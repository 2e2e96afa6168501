"""A configuration's data set: the generator module its ``data.module``
names, such as ``"bench.lib.watdiv"``.

The harness takes from the module only what this contract lists:

- ``generate(scale, seed) -> graph``: the triples from ``seed``, where
  ``graph`` has ``s``, ``p`` and ``o`` (int64 arrays of entity and
  predicate ids, one triple per row, no row twice), ``entities`` (entity
  ``i``'s name is ``entities[i]``), ``predicates`` (a list of names) and
  ``num_triples``;
- ``TEMPLATES``: the query templates, by name;
- ``instantiate(graph, name, rng) -> (patterns, text) | None``: one query
  of template ``name`` with its constants drawn from ``rng``, a
  ``numpy.random.Generator``; ``patterns`` holds (s, p, o) with ints for
  constants and ``"?v"`` strings for variables, every predicate constant,
  and ``text`` is the SPARQL SELECT of every variable; ``None`` where the
  graph holds nothing to fill a slot;
- ``workload_sparql(graph, n_queries, seed, templates) -> list[str]``: a
  user's query history, for deployments that place data from one.

The same module, scale and seed give the same triples and the same
queries: everything a run measures is drawn from them.
"""

from __future__ import annotations

import importlib
from types import ModuleType

CONTRACT = ("generate", "TEMPLATES", "instantiate", "workload_sparql")


def load(config: dict) -> ModuleType:
    """The generator module the configuration's ``data.module`` names."""
    name = config["data"].get("module")
    if not name:
        raise ValueError(f"configuration {config.get('name')!r} names no "
                         f"data.module")
    module = importlib.import_module(name)
    missing = [a for a in CONTRACT if not hasattr(module, a)]
    if missing:
        raise ValueError(f"data module {name!r} lacks {', '.join(missing)}")
    return module
