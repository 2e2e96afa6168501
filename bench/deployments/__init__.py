"""Deployment builders, one module per ``deployment.kind`` of a
configuration file. Each module has ``build(config, dataset, graph, seed,
times)``, where ``dataset`` is the configuration's generator module
(``bench/lib/datasets.py``), returning a
:class:`bench.deployments.base.Deployment`."""
