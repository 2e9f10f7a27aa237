"""The benchmark's own library: everything the yardstick is made of.

Nothing here imports JAX or `kserve_tpu`: the parent process that drives a
cell must never touch the chip (one process per chip, and that process is
the server child).  The two pieces that do need JAX — the plain reference
forward and the xplane reduction — run as children under JAX_PLATFORMS=cpu
(`benchmark/reference/check.py`, `benchmark/kbench/xplane_reduce.py`).
"""
