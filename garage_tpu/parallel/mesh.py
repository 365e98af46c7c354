"""Device-mesh sharding for pod-level EC repair fan-out.

The storage protocol itself (quorums, gossip, anti-entropy) runs host-side
over DCN — the reference has no NCCL/MPI analog to port (SURVEY.md §2.3).
The TPU mesh is used where the math is: batched erasure coding shards
embarrassingly over blocks ("blocks" axis = the DP analog), with no
collectives.
"""

from __future__ import annotations


def make_mesh(n_devices: int | None = None, axis: str = "blocks"):
    """1-D mesh over the first n devices (or all)."""
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise RuntimeError(
                f"need {n_devices} devices, jax sees {len(devs)} "
                f"{devs[0].platform} device(s)"
            )
        devs = devs[:n_devices]
    import numpy as np

    return Mesh(np.array(devs), (axis,))
