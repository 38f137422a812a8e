"""Client loops, one module per loop, found by the ``loop`` a traffic
mix names. Each exposes ``length(traffic, seconds)``, the deltas per
tenant a window of ``seconds`` can take, and ``run(fleet, feed,
traffic, seconds, top_k, span)``, which drives the window and returns
a `bench.harness.Window`."""
