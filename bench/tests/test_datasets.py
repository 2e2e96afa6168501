"""A configuration brings its data set by naming a generator module.

- A second data set (``tiny_dataset``, tests only) runs end to end through
  ``run_cell`` at a rehearsal's scale, and every answer agrees with the
  plain reference.
- ``watdivlike-10m-cloud`` still draws what it drew before data sets were
  named: the same triples, dictionary and first 300 request texts of
  ``cloud10m-complex`` at scale 50 (digests recorded from the harness as
  it was when ``bench.lib.watdiv`` was imported directly).
- A cell that cannot run fails when it is loaded, before any set-up.
- Where the requests run out, the error says which supply did.
"""

import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import run  # noqa: E402
from bench.lib import datasets, traffic  # noqa: E402

TINY_CONFIG = {
    "name": "tiny-cloud",
    "data": {"module": "bench.tests.tiny_dataset", "scale": 1},
    "deployment": {"kind": "cloud", "shards": 2, "backend": "jax"},
}
TINY_MIX = {"loop": "closed", "clients": 2, "warmup": 2, "requests": 150,
            "templates": ["friends_city"], "constants": "fresh", "users": 1}


def _digest(parts) -> str:
    h = hashlib.sha256()
    for part in parts:
        h.update(part)
    return h.hexdigest()


def test_second_data_set_runs_end_to_end():
    import jax

    jax.config.update("jax_platforms", "cpu")
    cell = {"cell": {"name": "tiny-cloud.friends", "config": "tiny-cloud",
                     "traffic": "tiny", "chips": 1},
            "config": TINY_CONFIG, "dataset": datasets.load(TINY_CONFIG),
            "traffic": TINY_MIX,
            "end_to_end": [], "per_layer": []}
    rec = run.run_cell(cell, seed=2**31 + 17, seconds=0.05, trace=False,
                       scale=1)
    assert rec["correct"] is True, rec["check"]
    assert rec["window"]["attempted"] > 0
    assert rec["window"]["correct_answers"] == rec["window"]["attempted"]


# seed: (first 300 request texts, triples s|p|o as little-endian int64)
PARENT_DIGESTS = {
    7: ("39b1733e5ece2ecacad0ea2a595d8b0165bb8144d78908a4d6de7b58926853b6",
        "62f49255a3feb4dedeb1f3bfb1b36faea5b9200cfd3bcd4d2710f318ea7b0fe8"),
    2**31 + 5: (
        "0ce21b2b6d621b922703bb80be6d68584b6b4f50700e8b363458adaa00ba9fdb",
        "562bc2222f93662b69774523b00e08faa80e731facd3e8e6177afe9d980e8ab6"),
}
# entity names, then predicate names, one a line: the same for every seed
PARENT_DICTIONARY = \
    "2207756e14fa3eb3dccec98489e0075bca82840972fdb562429119c933b5f59a"


@pytest.mark.parametrize("seed", sorted(PARENT_DIGESTS))
def test_cloud_config_draws_the_parents_requests(seed):
    cell = run.load_cell("cloud10m-complex")
    dataset = cell["dataset"]
    graph = dataset.generate(50, seed)
    requests = traffic.Requests(cell["traffic"], dataset, graph, seed)
    texts = "\n".join(requests[j].text for j in range(300))
    want_texts, want_triples = PARENT_DIGESTS[seed]
    assert _digest([texts.encode()]) == want_texts
    assert _digest(a.astype("<i8").tobytes()
                   for a in (graph.s, graph.p, graph.o)) == want_triples
    names = "\n".join(list(graph.entities) + graph.predicates)
    assert _digest([names.encode()]) == PARENT_DICTIONARY


def _spec(tmp_path, config: dict, traffic_name: str) -> dict:
    path = tmp_path / "config.json"
    path.write_text(json.dumps(config))
    return {"configs": [{"name": config["name"], "file": str(path)}],
            "workloads": [{"name": "probe", "config": config["name"],
                           "traffic": traffic_name, "chips": 1}],
            "end_to_end": [], "per_layer": []}


@pytest.mark.parametrize("case", ["unknown_template", "no_data_module"])
def test_a_cell_that_cannot_run_fails_at_load(tmp_path, case):
    if case == "unknown_template":
        # complex-fresh names "complex", which the tiny data set lacks
        spec = _spec(tmp_path, TINY_CONFIG, "complex-fresh")
        match = r"\['complex'\] are not in bench\.tests\.tiny_dataset"
    else:
        config = dict(TINY_CONFIG, data={"scale": 1})
        spec = _spec(tmp_path, config, "complex-fresh")
        match = "names no data.module"
    with pytest.raises(ValueError, match=match):
        run.load_cell("probe", spec)


def test_exhaustion_says_which_supply_ran_out():
    dataset = datasets.load(TINY_CONFIG)
    graph = dataset.generate(0.05, 3)             # 10 people, 10 texts
    requests = traffic.Requests(dict(TINY_MIX, requests=50), dataset,
                                graph, 3)
    with pytest.raises(traffic.Exhausted, match="distinct supply of "
                       "template 'friends_city' ran out at request 10"):
        requests[20]
    short = traffic.Requests(dict(TINY_MIX, requests=8), dataset, graph, 3)
    with pytest.raises(traffic.Exhausted,
                       match=r"traffic file's 'requests' \(8\) ran out"):
        short.window()[4]


def test_supply_is_the_last_count_within_the_draw_limit():
    # 500 requests a mean, at most 10 draws: up to 155 of 30 draws among
    # 345 of one, so the count ends 155 requests into the costly run
    assert traffic.SUPPLY_SPAN == 500 and traffic.MAX_NEW_TEXT_DRAWS == 10
    assert traffic.supply([1] * 1000 + [30] * 300) == 1155
    assert traffic.supply([1] * 1000 + [10] * 1000 + [20] * 500) == 2000
    assert traffic.supply([11] * 600) == 0
    graph = datasets.load(TINY_CONFIG).generate(0.05, 3)
    costs = traffic.draw_costs(TINY_MIX, datasets.load(TINY_CONFIG), graph,
                               3, 50)
    assert len(costs) == 10 and costs[0] == 1
    assert np.all(np.asarray(costs) >= 1)
