"""Deployment builders, one module per ``deployment.kind`` of a
configuration file. Each module has ``build(config, graph, seed, times)``
returning a :class:`bench.deployments.base.Deployment`."""
