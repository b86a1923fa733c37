"""ShardCtx: the one object the model/launch layers carry around.

It names the mesh, which axes are the data-parallel "worker" axes (each
data shard is one Byzantine-fault-containment unit, paper Sec. 2), which
axis is tensor-parallel, and which MoE implementation to use. It is a
frozen dataclass so call sites can ``dataclasses.replace`` it (the tests
flip ``moe_impl`` that way) and so it hashes as a jit-static closure.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

DATA_AXES_ORDER = ("pod", "data")    # leading axis is the pod-level DP axis


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Mesh context threaded through model forward / train / serve code."""

    mesh: Any = None
    batch_axes: Tuple[str, ...] = ()     # manual worker axes (DP)
    model_axis: Optional[str] = "model"  # TP axis (None: no model axis)
    moe_impl: str = "tp"                 # "tp" | "ep" | "local"
    remat: str = "full"                  # "full" | "save_psum"
    layer_gather: Optional[Callable] = None   # FSDP just-in-time gather
    global_batch: int = 0

    @property
    def num_workers(self) -> int:
        """Product of the data-axis sizes (1 without a mesh)."""
        if self.mesh is None or not self.batch_axes:
            return 1
        sizes = self.mesh.shape
        n = 1
        for ax in self.batch_axes:
            n *= sizes[ax]
        return n


def make_shard_ctx(mesh, global_batch: int, moe_impl: str = "tp"
                   ) -> ShardCtx:
    """Build the ShardCtx for ``mesh``: data axes = pod+data, model = TP."""
    if mesh is None:
        return ShardCtx(mesh=None, batch_axes=(), model_axis=None,
                        moe_impl=moe_impl, global_batch=global_batch)
    sizes = mesh.shape
    batch_axes = tuple(a for a in DATA_AXES_ORDER if a in sizes)
    n_workers = 1
    for a in batch_axes:
        n_workers *= sizes[a]
    if batch_axes and global_batch % n_workers:
        raise ValueError(
            f"global_batch={global_batch} must divide over the "
            f"{n_workers} data-parallel workers of axes {batch_axes}")
    model_axis = "model" if "model" in sizes else None
    return ShardCtx(mesh=mesh, batch_axes=batch_axes, model_axis=model_axis,
                    moe_impl=moe_impl, global_batch=global_batch)
