"""A cloud-only SPARQL service: one ``SparqlEndpoint`` with the JAX
backend over a predicate-sharded store staged on the device."""

from __future__ import annotations

from .base import Deployment, dictionary_of, timed


def build(config: dict, graph, seed: int, times: dict) -> Deployment:
    from repro.rdf.sharding import ShardedTripleStore
    from repro.sparql.endpoint import SparqlEndpoint
    from repro.sparql.engine import JaxBackend, QueryEngine

    dep = config["deployment"]
    dictionary = timed(times, "dictionary", lambda: dictionary_of(graph))
    store = timed(times, "shard", lambda: ShardedTripleStore(
        graph.s, graph.p, graph.o, len(graph.entities),
        len(graph.predicates), num_shards=dep["shards"]))
    backend = JaxBackend()
    timed(times, "stage", lambda: backend.stage(store))
    return Deployment(SparqlEndpoint(store, dictionary,
                                     engine=QueryEngine(backend=backend)))
