"""The benchmark: cells of BENCHMARK.json, their traffic, the reference
that decides `correct`, and the reduction of traces to metrics."""
