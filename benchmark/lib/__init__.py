"""The yardstick: traffic generation, metric arithmetic, peaks, FLOPs and
bytes, the trace reducer and the plain references.  Later PRs add files
beside these and edit none."""
