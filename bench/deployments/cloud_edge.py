"""The source paper's cloud-edge deployment: a cloud store and K edge
servers holding pattern-induced subgraphs placed from each user's query
history, every batch scheduled across them by branch and bound."""

from __future__ import annotations

from ..lib import watdiv
from .base import Deployment, dictionary_of, timed


class CloudEdge(Deployment):
    def __init__(self, endpoint, policy: str) -> None:
        self.queue_kw = {"mode": "round", "mode_kw": {"policy": policy}}
        super().__init__(endpoint)


def build(config: dict, graph, seed: int, times: dict) -> CloudEdge:
    from repro.core.cost import SystemParams
    from repro.edge.system import EdgeCloudSystem
    from repro.rdf.sharding import ShardedTripleStore
    from repro.sparql.endpoint import SparqlEndpoint
    from repro.sparql.engine import JaxBackend, QueryEngine

    dep = config["deployment"]
    dictionary = timed(times, "dictionary", lambda: dictionary_of(graph))
    store = timed(times, "shard", lambda: ShardedTripleStore(
        graph.s, graph.p, graph.o, len(graph.entities),
        len(graph.predicates), num_shards=dep["shards"]))
    hist = dep["history"]
    history = [watdiv.workload_sparql(graph, hist["queries_per_user"],
                                      seed=[seed, 1000 + n],
                                      templates=hist["templates"])
               for n in range(dep["users"])]
    params = SystemParams.synthetic(n_users=dep["users"],
                                    n_edges=dep["edges"],
                                    seed=dep["params_seed"])
    backend = JaxBackend()
    system = EdgeCloudSystem(store, dictionary, params,
                             storage_budgets=dep["edge_budget_bytes"],
                             engine=QueryEngine(backend=backend))
    timed(times, "place", lambda: system.prepare(history))
    timed(times, "stage", lambda: [backend.stage(st) for st in
                                   [store] + [es.store for es in system.edges
                                              if es.store is not None]])
    return CloudEdge(SparqlEndpoint.from_system(system), dep["policy"])
