"""Deployment generators, one module per configuration kind, found by
the ``generator`` a configuration file names. Each exposes
``generate(config, length, seed) -> List[common.Tenant]``, every tenant
with a stream of ``length`` deltas."""
