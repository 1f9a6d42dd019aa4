"""Shared code of the chip benchmark: cell discovery, graph generation,
the plain reference, the runners of the measured window, the trace
reduction and the table of device peaks.

Nothing here imports the program except through its public API
(``repro.core``, ``repro.graphs.shard_graph``, ``repro.serve``)."""
