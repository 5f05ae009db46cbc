"""The system under test, built from a configuration and a traffic file:
the only place the harness calls into the program's front doors
(``SamplerSession``, ``SearchSession``, ``SearchServer``)."""
from __future__ import annotations

from typing import Any, Dict

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


def search_config(config: Dict[str, Any], query_chunk: int):
    from repro.retrieval.search_core import SearchConfig
    return SearchConfig(engine=config["engine"], backend=config["backend"],
                        query_chunk=query_chunk)


def search_server(corpus_provider, config: Dict[str, Any],
                  traffic: Dict[str, Any]):
    """A one-tenant ``SearchServer`` over the configuration's index."""
    from repro.serve import SchedulerConfig, SearchServer
    sched = SchedulerConfig(max_queue=traffic["max_queue"],
                            max_batch=traffic["max_batch"],
                            k_max=traffic["k_max"])
    return SearchServer(corpus_provider,
                        config=search_config(config, traffic["max_batch"]),
                        scheduler=sched, max_tenants=1)
