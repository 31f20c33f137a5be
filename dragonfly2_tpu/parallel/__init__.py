"""Device-mesh parallel plans: pod topology + ICI shard redistribution.

No reference analog (Dragonfly2 has no device compute); this is the TPU-first
layer from BASELINE.json: once one host of a slice holds a piece in HBM,
redistribution inside the slice rides ICI collectives instead of the NIC.

The package imports only the topology detection, which reads the
environment: every daemon imports it, and a daemon without a device sink
must not import jax (a chip belongs to one process). The collectives are in
``dragonfly2_tpu.parallel.ici``, imported by whoever holds the device.
"""

from dragonfly2_tpu.parallel.topology import TpuTopology, detect_topology

__all__ = ["TpuTopology", "detect_topology"]
