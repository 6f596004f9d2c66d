"""The benchmark's own code: traffic, windows, weights, plain references,
the comparison that decides ``correct``, the trace reducer and the peaks.
Nothing here imports the program; only ``serve.py`` and ``train.py`` do."""
