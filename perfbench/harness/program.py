"""The system under test, built from a configuration and a traffic file:
the only place the harness calls into the program's front doors
(``SamplerSession``, ``SearchSession``, ``SearchServer``)."""
from __future__ import annotations

from typing import Any, Dict, Sequence

import numpy as np


def draw_seed(seed: int, i: int) -> int:
    """The ``i``-th draw seed of a run, below 2**31, from the run's seed."""
    return int(np.random.default_rng([seed, i]).integers(0, 2**31 - 1))


def qrel_table(inputs) -> Any:
    """The judgments on the device, every row valid."""
    import jax.numpy as jnp
    from repro.core import QRelTable
    q = jnp.asarray(inputs.query_ids)
    return QRelTable(q, jnp.asarray(inputs.entity_ids),
                     jnp.asarray(inputs.scores), jnp.ones(q.shape, bool))


def sampler_session(table, config: Dict[str, Any], seed: int):
    """A ``SamplerSession`` with the configuration's pipeline settings and
    default draw (``sample_fraction`` at ``seed``)."""
    from repro.core import SamplerSession, SamplerSpec
    spec = SamplerSpec(strategy="windtunnel", engine=config["engine"],
                       tau_quantile=config["tau_quantile"],
                       fanout=config["fanout"],
                       lp_rounds=config["lp_rounds"],
                       max_degree=config["max_degree"],
                       target_size=config["sample_fraction"], seed=seed)
    return SamplerSession(table, num_queries=config["num_queries"],
                          num_entities=config["num_entities"], spec=spec)


#: the mesh axis a corpus is row-sharded over; a corpus axis of the
#: program's retrieval rules (``distributed/sharding.RETRIEVAL_RULES``)
CORPUS_AXIS = "data"


def _mesh(devices: Sequence[Any]):
    from jax.sharding import Mesh
    return Mesh(np.asarray(devices), (CORPUS_AXIS,))


def search_config(config: Dict[str, Any], query_chunk: int,
                  devices: Sequence[Any]):
    """The configuration's ``SearchConfig``; over several devices, the
    sharded-from-birth plans on a 1-D mesh of them."""
    from repro.retrieval.search_core import SearchConfig
    several = len(devices) > 1
    return SearchConfig(engine=config["engine"], backend=config["backend"],
                        query_chunk=query_chunk, sharded=several,
                        streamed=several,
                        mesh=_mesh(devices) if several else None)


def search_corpus(rows, config: Dict[str, Any], devices: Sequence[Any]):
    """What ``SearchSession`` is given for a maker's rows: the rows on one
    device; over several, a born ``ShardedCorpus`` where the maker made
    them row-sharded over the devices in its layout (ceil(rows / d) * d
    rows, zero past ``rows``), with no copy; else the rows streamed in
    from the host."""
    if len(devices) == 1:
        return rows
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.sharded_corpus import ShardedCorpus
    mesh = _mesh(devices)
    n, d = int(config["rows"]), len(devices)
    sharding = NamedSharding(mesh, P(CORPUS_AXIS, None))
    if (isinstance(rows, jax.Array) and rows.shape[0] == -(-n // d) * d
            and rows.sharding.is_equivalent_to(sharding, rows.ndim)):
        return ShardedCorpus(jax.make_array_from_single_device_arrays(
            rows.shape, sharding, [s.data for s in rows.addressable_shards]),
            n, mesh, (CORPUS_AXIS,))
    return ShardedCorpus.from_host(rows, mesh=mesh)


def search_server(corpus_provider, config: Dict[str, Any],
                  traffic: Dict[str, Any], devices: Sequence[Any]):
    """A one-tenant ``SearchServer`` over the configuration's index.  Its
    tenant (``serve/ingest.LiveIndex``) keeps the corpus on the host, and
    over several devices streams it in from there, so it is given the
    corpus's first ``rows`` rows."""
    from repro.serve import SchedulerConfig, SearchServer
    sched = SchedulerConfig(max_queue=traffic["max_queue"],
                            max_batch=traffic["max_batch"],
                            k_max=traffic["k_max"])
    n = int(config["rows"])

    def rows(tenant: str):
        made = corpus_provider(tenant)
        return made if len(devices) == 1 else np.asarray(made)[:n]

    return SearchServer(rows, config=search_config(
        config, traffic["max_batch"], devices), scheduler=sched,
        max_tenants=1)
