"""Cell builders: one (architecture x input-shape x mesh) dry-run cell.

``build_cell`` returns the jitted step function plus ShapeDtypeStruct
argument specs carrying NamedShardings — exactly what
``jax.jit(fn).lower(*args)`` needs, with zero real allocation. The SAME
builders power the smoke tests (reduced configs on a 1-device mesh with
real arrays) and the launchers, so the dry-run proves the code path that
actually trains/serves.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_arch
from repro.distributed.sharding import (GNN_RULES, LM_RULES, RECSYS_RULES,
                                        logical_to_spec, tree_shardings)
from repro.models import recsys as rs
from repro.models import mace as mc
from repro.models import transformer as tf
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update


@dataclasses.dataclass
class Cell:
    arch_id: str
    shape_name: str
    fn: Callable            # jitted
    args: tuple             # ShapeDtypeStructs with shardings (for lower)
    kind: str               # train | prefill | decode | serve | retrieval
    model_flops_per_step: float  # 6*N*D style estimate (§Roofline)
    donate: tuple = ()


def _sds(tree, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree, shardings)


def _replicated(mesh, tree):
    rep = NamedSharding(mesh, P())
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                       sharding=rep), tree)


def _batch_spec(mesh):
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return axes if len(axes) > 1 else (axes[0] if axes else None)


def _divisible_axes(mesh, b: int) -> tuple:
    """Largest prefix-trimmed ('pod','data') axis set whose product divides
    the batch (batch=1 decode cells replicate their batch dim)."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    while axes and b % int(np.prod([sizes[a] for a in axes])) != 0:
        axes = axes[1:]
    return axes


def _axes_or_none(axes: tuple):
    return axes if len(axes) > 1 else (axes[0] if axes else None)


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------

def _lm_param_specs(mesh, cfg, dtype=None, rules_override=None):
    shapes = jax.eval_shape(lambda k: tf.init_transformer(k, cfg),
                            jax.random.PRNGKey(0))
    if dtype is not None:
        shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, dtype), shapes)
    rules = {**LM_RULES, **(rules_override or {})}
    shard = tree_shardings(mesh, tf.param_logical_axes(cfg), rules)
    return _sds(shapes, shard), shard


def _lm_opt_specs(mesh, params_sds, param_shard):
    opt_shapes = jax.eval_shape(adamw_init, params_sds)
    rep = NamedSharding(mesh, P())
    opt_shard = {"m": param_shard, "v": param_shard, "step": rep}
    return _sds(opt_shapes, opt_shard)


def _cache_specs(mesh, cfg, batch, max_seq):
    shapes = jax.eval_shape(
        lambda: tf.init_kv_cache(cfg, batch, max_seq))
    b_ax = _axes_or_none(_divisible_axes(mesh, batch))
    model_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    # shard kv heads over 'model' when they divide; else the head_dim; the
    # rolling (L, B, S, Hkv, Dh) cache is the decode-cell memory budget
    if cfg.n_kv_heads % model_size == 0:
        kv_spec = NamedSharding(mesh, P(None, b_ax, None, "model", None))
    elif cfg.head_dim % model_size == 0:
        kv_spec = NamedSharding(mesh, P(None, b_ax, None, None, "model"))
    else:
        kv_spec = NamedSharding(mesh, P(None, b_ax, None, None, None))
    pos_spec = NamedSharding(mesh, P(b_ax))
    return _sds(shapes, {"k": kv_spec, "v": kv_spec, "pos": pos_spec})


def lm_model_flops(cfg, n_tokens, kind):
    n_active = tf.active_params(cfg)
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_active * n_tokens


def build_lm_cell(arch_id, shape_name, mesh, *, reduced=False,
                  overrides: Optional[dict] = None) -> Cell:
    spec = get_arch(arch_id)
    cfg = spec.make_reduced() if reduced else spec.make_config()
    # activation sharding constraints (see transformer._sc): batch over
    # pod+data, heads/ffn/vocab over model
    b_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    model_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    shape0 = spec.shapes[shape_name]
    sp = (shape0["kind"] in ("train", "prefill")
          and shape0["seq_len"] % max(model_size, 1) == 0 and not reduced)
    cfg = dataclasses.replace(
        cfg, act_batch_axes=b_axes or None,
        act_model_axis="model" if "model" in mesh.axis_names else None,
        seq_parallel=sp)
    cfg_overrides = {k: v for k, v in (overrides or {}).items()
                     if k != "microbatches"}
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = dict(spec.shapes[shape_name])
    if reduced:
        shape.update({"seq_len": min(shape["seq_len"], 64),
                      "global_batch": min(shape["global_batch"], 4)})
    kind = shape["kind"]
    b, s = shape["global_batch"], shape["seq_len"]
    d_axes = _divisible_axes(mesh, b)
    b_ax = _axes_or_none(d_axes)
    cfg = dataclasses.replace(cfg, act_batch_axes=d_axes or None)

    if kind == "train":
        params_sds, param_shard = _lm_param_specs(
            mesh, cfg, rules_override=spec.rules_override)
        opt_sds = _lm_opt_specs(mesh, params_sds, param_shard)
        tokens = jax.ShapeDtypeStruct(
            (b, s + 1), jnp.int32, sharding=NamedSharding(mesh, P(b_ax, None)))
        opt_cfg = AdamWConfig()
        # §Perf lever: microbatched gradient accumulation — activation and
        # dispatch temps scale with the per-microbatch batch; the grad
        # all-reduce of microbatch i overlaps microbatch i+1's forward
        mb = int((overrides or {}).get("microbatches", 1))

        def train_step(params, opt_state, tokens):
            if mb > 1:
                mbt = tokens.reshape(mb, b // mb, s + 1)

                def one(acc, t):
                    loss, g = jax.value_and_grad(tf.lm_loss)(params, t, cfg)
                    return jax.tree.map(
                        lambda a_, g_: a_ + g_.astype(jnp.float32) / mb,
                        acc, g), loss

                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                grads, losses = jax.lax.scan(one, zeros, mbt)
                loss = losses.mean()
            else:
                loss, grads = jax.value_and_grad(tf.lm_loss)(params, tokens,
                                                             cfg)
            params, opt_state, info = adamw_update(grads, opt_state, params,
                                                   opt_cfg)
            return params, opt_state, loss

        return Cell(arch_id, shape_name, jax.jit(train_step,
                                                 donate_argnums=(0, 1)),
                    (params_sds, opt_sds, tokens), kind,
                    lm_model_flops(cfg, b * s, "train"), donate=(0, 1))

    serve_dtype = cfg.dtype
    params_sds, _ = _lm_param_specs(mesh, cfg, dtype=serve_dtype,
                                    rules_override=spec.rules_override)
    if kind == "prefill":
        tokens = jax.ShapeDtypeStruct(
            (b, s), jnp.int32, sharding=NamedSharding(mesh, P(b_ax, None)))

        def prefill_step(params, tokens):
            return tf.prefill(params, tokens, cfg)

        return Cell(arch_id, shape_name, jax.jit(prefill_step),
                    (params_sds, tokens), kind,
                    lm_model_flops(cfg, b * s, "prefill"))

    # decode: one new token against a seq_len-deep KV cache
    cache_sds = _cache_specs(mesh, cfg, b, s)
    tokens = jax.ShapeDtypeStruct(
        (b, 1), jnp.int32, sharding=NamedSharding(mesh, P(b_ax, None)))

    def decode(params, cache, tokens):
        return tf.decode_step(params, cache, tokens, cfg)

    return Cell(arch_id, shape_name, jax.jit(decode, donate_argnums=(1,)),
                (params_sds, cache_sds, tokens), kind,
                lm_model_flops(cfg, b, "decode"), donate=(1,))


# ---------------------------------------------------------------------------
# RecSys cells
# ---------------------------------------------------------------------------

def _recsys_param_specs(mesh, cfg):
    shapes = jax.eval_shape(lambda k: rs.init_recsys(k, cfg),
                            jax.random.PRNGKey(0))
    table_spec = NamedSharding(mesh, P("model", None))
    rep = NamedSharding(mesh, P())

    def shard_for(path, leaf):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if "table" in name:
            # rows over the whole grid: the 96GB Criteo-TB tables + AdamW
            # slots must split 256 ways, not 16 (measured 16GB/dev at 16)
            return NamedSharding(mesh, P(tuple(
                a for a in ("model", "data") if a in mesh.axis_names), None))
        return rep

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    shard = treedef.unflatten([shard_for(p, l) for p, l in flat])
    return _sds(shapes, shard), shard


def _recsys_batch(mesh, cfg, batch):
    b_ax = _axes_or_none(_divisible_axes(mesh, batch))
    bs = lambda shape, dt: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, P(b_ax, *([None] * (len(shape) - 1)))))
    if cfg.arch == "dien":
        return {
            "target_item": bs((batch,), jnp.int32),
            "target_cat": bs((batch,), jnp.int32),
            "hist_items": bs((batch, cfg.seq_len), jnp.int32),
            "hist_cats": bs((batch, cfg.seq_len), jnp.int32),
            "hist_mask": bs((batch, cfg.seq_len), jnp.float32),
            "label": bs((batch,), jnp.float32),
        }
    out = {"sparse": bs((batch, cfg.n_sparse), jnp.int32),
           "label": bs((batch,), jnp.float32)}
    if cfg.n_dense:
        out["dense"] = bs((batch, cfg.n_dense), jnp.float32)
    return out


def build_recsys_cell(arch_id, shape_name, mesh, *, reduced=False,
                      overrides=None) -> Cell:
    spec = get_arch(arch_id)
    cfg = spec.make_reduced() if reduced else spec.make_config()
    cfg_overrides = {k: v for k, v in (overrides or {}).items()
                     if k != "sharded_topk"}
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = dict(spec.shapes[shape_name])
    if reduced:
        shape["batch"] = min(shape["batch"], 8)
        shape["n_candidates"] = min(shape.get("n_candidates", 0), 512)
    kind = shape["kind"]
    b = shape["batch"]
    params_sds, param_shard = _recsys_param_specs(mesh, cfg)
    batch_sds = _recsys_batch(mesh, cfg, b)

    # rough flops: embedding gathers + MLP/attention matmuls (dense dims)
    flops = _recsys_flops(cfg, b)

    if kind == "train":
        opt_sds = _lm_opt_specs(mesh, params_sds, param_shard)
        opt_cfg = AdamWConfig()

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(rs.bce_loss)(params, batch, cfg)
            params, opt_state, info = adamw_update(grads, opt_state, params,
                                                   opt_cfg)
            return params, opt_state, loss

        return Cell(arch_id, shape_name,
                    jax.jit(train_step, donate_argnums=(0, 1)),
                    (params_sds, opt_sds, batch_sds), kind, 3 * flops,
                    donate=(0, 1))

    if kind == "serve":
        def serve_step(params, batch):
            return rs.recsys_forward(params, batch, cfg)

        return Cell(arch_id, shape_name, jax.jit(serve_step),
                    (params_sds, batch_sds), kind, flops)

    # retrieval: 1 query batch x n_candidates, fused top-k
    nc = shape["n_candidates"]
    grid = tuple(a for a in ("model", "data") if a in mesh.axis_names)
    grid_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    grid_n = int(np.prod([grid_sizes[a] for a in grid])) or 1
    k_top = min(100, nc)
    sharded_topk = (overrides or {}).get("sharded_topk", False)
    model_size = grid_sizes.get("model", 1)
    if sharded_topk == "local":
        nc = ((nc + grid_n - 1) // grid_n) * grid_n   # pad to the grid
    cand_spec = P(grid) if sharded_topk == "local" else P("model")
    cand = jax.ShapeDtypeStruct(
        (nc,), jnp.int32, sharding=NamedSharding(mesh, cand_spec))
    batch_sds.pop("label")

    def retrieval_step(params, batch, candidate_ids):
        if sharded_topk == "local" and nc % grid_n == 0:
            # §Perf lever 2: shard-local candidate pools — each shard
            # scores candidates resident in ITS table rows (production
            # sharded-ANN layout), so the 512MB cross-shard row
            # gather/all-reduce disappears; only (grid x k) merge payloads
            # cross the wire.
            from jax import shard_map
            u = rs.user_vector(params, batch, cfg)          # (B, D) replicated
            items = rs.item_matrix(params, cfg)             # rows grid-sharded

            def local_score(u_, table_l, cand_l):
                rows = table_l.shape[0]
                it = jnp.take(table_l, cand_l % rows, axis=0)
                s = u_ @ it.T                               # (B, nc/grid)
                ls, li = jax.lax.top_k(s, k_top)
                shard = jax.lax.axis_index(grid[0])
                if len(grid) > 1:
                    shard = shard * grid_sizes[grid[1]] + \
                        jax.lax.axis_index(grid[1])
                li = li + shard * cand_l.shape[0]
                return ls, li

            ls, li = shard_map(
                local_score, mesh=mesh,
                in_specs=(P(), P(grid, None), P(grid)),
                out_specs=(P(None, grid), P(None, grid)))(
                u, items, candidate_ids)
            top_s, pos = jax.lax.top_k(ls, k_top)
            return top_s, jnp.take_along_axis(li, pos, axis=1)
        scores = rs.retrieval_scores(params, batch, cfg, candidate_ids)
        if sharded_topk and nc % model_size == 0:
            # §Perf lever: per-shard local top-k then merge — the global
            # lax.top_k over a model-sharded axis otherwise all-gathers the
            # full (B, n_candidates) score matrix
            from jax import shard_map

            def local_topk(s):
                ls, li = jax.lax.top_k(s, k_top)
                li = li + jax.lax.axis_index("model") * s.shape[-1]
                return ls, li

            ls, li = shard_map(
                local_topk, mesh=mesh,
                in_specs=P(None, "model"),
                out_specs=(P(None, "model"), P(None, "model")))(scores)
            top_s, pos = jax.lax.top_k(ls, k_top)
            return top_s, jnp.take_along_axis(li, pos, axis=1)
        return jax.lax.top_k(scores, k_top)

    d = rs.item_matrix_dim(cfg)
    return Cell(arch_id, shape_name, jax.jit(retrieval_step),
                (params_sds, batch_sds, cand), kind, 2.0 * b * nc * d)


def _recsys_flops(cfg, b):
    if cfg.arch == "dlrm":
        dims = [cfg.n_dense] + list(cfg.bot_mlp)
        f = sum(2 * a * c for a, c in zip(dims[:-1], dims[1:]))
        n_f = cfg.n_sparse + 1
        f += 2 * n_f * n_f * cfg.embed_dim
        top_in = n_f * (n_f - 1) // 2 + cfg.embed_dim
        dims = [top_in] + list(cfg.top_mlp)
        f += sum(2 * a * c for a, c in zip(dims[:-1], dims[1:]))
        return b * f
    if cfg.arch == "dcn_v2":
        d0 = cfg.n_dense + cfg.n_sparse * cfg.embed_dim
        f = cfg.n_cross_layers * 2 * d0 * d0
        dims = [d0] + list(cfg.mlp_dims)
        f += sum(2 * a * c for a, c in zip(dims[:-1], dims[1:]))
        return b * f
    if cfg.arch == "autoint":
        fdim = cfg.n_sparse
        f = 0
        in_d = cfg.embed_dim
        for _ in range(cfg.n_attn_layers):
            hd = cfg.n_heads * cfg.d_attn
            f += fdim * (4 * 2 * in_d * hd) + 2 * fdim * fdim * hd * 2
            in_d = hd
        return b * f
    if cfg.arch == "dien":
        in_d, hd = 2 * cfg.embed_dim, cfg.gru_dim
        per_step = 2 * 3 * hd * (in_d + hd) * 2   # gru1 + augru
        return b * cfg.seq_len * per_step
    return b * 1e6


# ---------------------------------------------------------------------------
# GNN (MACE) cells
# ---------------------------------------------------------------------------

def _mace_batch_sds(mesh, n_nodes, n_edges, d_feat, n_graphs, node_loss):
    grid = tuple(a for a in ("data", "model") if a in mesh.axis_names)
    grid = grid if len(grid) > 1 else (grid[0] if grid else None)
    nd = lambda shape: NamedSharding(mesh, P(grid, *([None] * (len(shape) - 1))))
    out = {
        "positions": jax.ShapeDtypeStruct((n_nodes, 3), jnp.float32,
                                          sharding=nd((n_nodes, 3))),
        "node_feats": jax.ShapeDtypeStruct((n_nodes, d_feat), jnp.float32,
                                           sharding=nd((n_nodes, d_feat))),
        "edge_src": jax.ShapeDtypeStruct((n_edges,), jnp.int32,
                                         sharding=nd((n_edges,))),
        "edge_dst": jax.ShapeDtypeStruct((n_edges,), jnp.int32,
                                         sharding=nd((n_edges,))),
        "edge_mask": jax.ShapeDtypeStruct((n_edges,), jnp.bool_,
                                          sharding=nd((n_edges,))),
        "graph_ids": jax.ShapeDtypeStruct((n_nodes,), jnp.int32,
                                          sharding=nd((n_nodes,))),
    }
    if node_loss:
        out["node_target"] = jax.ShapeDtypeStruct(
            (n_nodes,), jnp.float32, sharding=nd((n_nodes,)))
        out["node_mask"] = jax.ShapeDtypeStruct(
            (n_nodes,), jnp.float32, sharding=nd((n_nodes,)))
    else:
        out["energy_target"] = jax.ShapeDtypeStruct(
            (n_graphs,), jnp.float32, sharding=NamedSharding(mesh, P()))
        out["force_target"] = jax.ShapeDtypeStruct(
            (n_nodes, 3), jnp.float32, sharding=nd((n_nodes, 3)))
    return out


def mace_flops(cfg, n_edges, n_nodes):
    import repro.models.so3 as so3
    paths = so3.valid_paths(cfg.l_max)
    path_f = sum((2 * l1 + 1) * (2 * l2 + 1) * (2 * l3 + 1)
                 for l1, l2, l3 in paths)
    per_edge = 2 * path_f * cfg.channels
    per_node = 2 * 2 * path_f * cfg.channels + 8 * cfg.channels ** 2
    return cfg.n_layers * (n_edges * per_edge + n_nodes * per_node)


def build_gnn_cell(arch_id, shape_name, mesh, *, reduced=False,
                   overrides=None) -> Cell:
    spec = get_arch(arch_id)
    cfg = spec.make_reduced() if reduced else spec.make_config()
    shape = dict(spec.shapes[shape_name])
    kind = shape["kind"]

    if kind == "train_sampled":
        # static padded block sizes from the fanout schedule
        bn = shape["batch_nodes"]
        f1, f2 = shape["fanouts"]
        n2 = bn * (f2 + 1)
        n_nodes = n2 * (f1 + 1)
        n_edges = bn * f2 + n2 * f1
        d_feat, n_graphs, node_loss = cfg.d_feat, 1, True
    else:
        n_nodes, n_edges = shape["n_nodes"], shape["n_edges"]
        d_feat = shape.get("d_feat", cfg.d_feat)
        n_graphs = shape.get("batch", shape.get("n_graphs", 1))
        if "batch" in shape:   # batched small graphs
            n_nodes, n_edges = n_nodes * n_graphs, n_edges * n_graphs
        node_loss = kind == "train_node"
    if reduced:
        n_nodes, n_edges = min(n_nodes, 64), min(n_edges, 256)
        d_feat, n_graphs = min(d_feat, 8), min(n_graphs, 2)
    # pad node/edge counts to the device-grid multiple (padded entries are
    # masked; the data model is already mask-based)
    grid_axes = tuple(a for a in ("data", "model") if a in mesh.axis_names)
    grid_n = int(np.prod([s for a, s in zip(mesh.axis_names,
                                            mesh.devices.shape)
                          if a in grid_axes])) or 1
    n_nodes = ((n_nodes + grid_n - 1) // grid_n) * grid_n
    n_edges = ((n_edges + grid_n - 1) // grid_n) * grid_n
    cfg = dataclasses.replace(cfg, d_feat=d_feat,
                              act_grid_axes=grid_axes or None)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)

    params_shapes = jax.eval_shape(lambda k: mc.init_mace(k, cfg),
                                   jax.random.PRNGKey(0))
    params_sds = _replicated(mesh, params_shapes)
    rep_shard = jax.tree.map(lambda s: s.sharding, params_sds)
    batch_sds = _mace_batch_sds(mesh, n_nodes, n_edges, d_feat, n_graphs,
                                node_loss)
    opt_sds = _lm_opt_specs(mesh, params_sds, rep_shard)
    opt_cfg = AdamWConfig()
    loss_fn = mc.mace_node_loss if node_loss else mc.mace_loss

    def train_step(params, opt_state, batch):
        batch = dict(batch, n_graphs=n_graphs)
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg)
        params, opt_state, info = adamw_update(grads, opt_state, params,
                                               opt_cfg)
        return params, opt_state, loss

    mult = 3.0 if node_loss else 7.0   # fwd+bwd (+force second-order)
    return Cell(arch_id, shape_name,
                jax.jit(train_step, donate_argnums=(0, 1)),
                (params_sds, opt_sds, batch_sds), "train",
                mult * mace_flops(cfg, n_edges, n_nodes), donate=(0, 1))


# ---------------------------------------------------------------------------

def build_cell(arch_id: str, shape_name: str, mesh, *, reduced=False,
               overrides=None) -> Cell:
    family = get_arch(arch_id).family
    builder = {"lm": build_lm_cell, "recsys": build_recsys_cell,
               "gnn": build_gnn_cell}[family]
    return builder(arch_id, shape_name, mesh, reduced=reduced,
                   overrides=overrides)
