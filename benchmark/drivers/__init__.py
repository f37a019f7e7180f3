"""One module per kind of traffic: `Cell` builds, warms, runs the window,
and reports end-to-end numbers, checks and per-layer context."""
