"""Per-replica weight ownership + real provisioning transports (§6).

The paper's Fast Scaling claim (Table 2) is that a scaled-out instance
pulls weights **device-to-device from a live replica** instead of
re-reading them from disk, cutting cold-start latency by an order of
magnitude.  For that claim to be testable on the engine plane, replicas
cannot alias one shared params tree — each
:class:`~repro.serving.engine.InferenceEngine` must *own* its weights,
and scale-out must actually move bytes through the selected transport.

:class:`WeightManager` is that ownership registry plus the three
Table-2 transports:

- ``d2d``  — pull from a live donor replica's params tree via
  ``jax.device_put`` onto the new replica's device (true D2D reshard
  when source and destination devices differ; an on-device copy — the
  single-host stand-in for an ICI pull — when they coincide, so the
  new replica never aliases the donor's buffers).
- ``cpu``  — copy from the host-resident offload of the seed params
  (host -> device over PCIe/host links).
- ``disk`` — load the seed checkpoint written via
  :mod:`repro.distributed.checkpoint` (the scale-from-zero path: it
  needs no live donor and no warm host copy).

Every provision is wall-clock measured and reported to the
:class:`~repro.core.tlmanager.TLManager`, whose
``weight_load_time`` then predicts from *observed* bandwidth — the
Scaler's Algorithm-3 tick picks the provisioning path from measured,
not analytic, costs.

Placement reuses the sharding plumbing: under an active
:func:`repro.distributed.sharding.use_rules` context the target keeps
the rules' mesh sharding; otherwise replicas round-robin over local
devices via ``SingleDeviceSharding`` (on a 1-device CPU host every
replica lands on the same device but still owns distinct buffers).
"""

from __future__ import annotations

import tempfile
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.checkpoint import (
    checkpoint_nbytes,
    load_checkpoint,
    save_checkpoint,
)
from repro.distributed.sharding import current_rules

STRATEGIES = ("d2d", "cpu", "disk")


def _owned_put(h: np.ndarray, sharding) -> jax.Array:
    """Host -> device copy the replica owns.  On an accelerator the put
    already yields a fresh device buffer; a CPU-device put of a host
    array may be zero-copy, so there (only) an explicit copy keeps the
    replica from sharing the offload's memory — and keeps an
    accelerator from holding each leaf twice."""
    x = jax.device_put(h, sharding)
    if all(d.platform == "cpu" for d in x.devices()):
        x = jnp.copy(x)
    return x


class WeightManager:
    """Owns the per-replica params trees of one served model.

    Only ``seed_params``' host offload and disk checkpoint are kept, as
    provisioning *source* material — never the device tree itself, so
    each replica holds the one device copy of its weights: ``adopt``
    registers a tree the caller hands over (the cluster's replica 0
    takes the seed tree this way), every ``provision`` materializes a
    fresh tree, and ``release`` drops it (scale-in reclaims the copy's
    memory).
    """

    def __init__(self, seed_params: Any, tl=None,
                 ckpt_dir: Optional[str] = None):
        self._owned: dict[int, Any] = {}
        self.tl = tl
        # "cpu" source: host-resident offload of the seed tree.  A real
        # copy, not np.asarray — on the CPU backend asarray zero-copies
        # the device buffer and the "offload" would alias the live tree
        self.host = jax.tree.map(lambda x: np.array(x), seed_params)
        self.nbytes = float(sum(leaf.nbytes
                                for leaf in jax.tree.leaves(self.host)))
        # "disk" source: a real checkpoint written through the same
        # atomic-write path training restores from (scale-from-zero
        # needs neither a donor nor a warm host copy — only this file)
        self._tmp = None
        if ckpt_dir is None:
            self._tmp = tempfile.TemporaryDirectory(prefix="hfx-weights-")
            ckpt_dir = self._tmp.name
        self.ckpt_dir = ckpt_dir
        save_checkpoint(self.ckpt_dir, 0, self.host)
        assert checkpoint_nbytes(self.ckpt_dir, 0) == self.nbytes

    # -- ownership registry ----------------------------------------------------
    def owns(self, wid: int) -> bool:
        return wid in self._owned

    def params_of(self, wid: int) -> Any:
        return self._owned[wid]

    def donors(self) -> list[int]:
        """Replicas a ``d2d`` provision could pull from right now."""
        return sorted(self._owned)

    def adopt(self, wid: int, params: Any) -> None:
        """Register an externally materialized tree the caller hands
        over (e.g. the seed tree, adopted by replica 0)."""
        if wid in self._owned:
            raise ValueError(f"replica {wid} already owns a params tree")
        self._owned[wid] = params

    def release(self, wid: int) -> None:
        """Scale-in: drop the replica's tree so its memory is
        reclaimable (and it stops being a d2d donor candidate)."""
        self._owned.pop(wid, None)

    # -- placement -------------------------------------------------------------
    def placement(self, wid: int):
        """Target sharding for replica ``wid``'s params.  Inside a
        sharding-rules context the mesh placement wins (a replica may
        span a TP device group); otherwise replicas round-robin over
        local devices."""
        rules = current_rules()
        if rules is not None and rules.mesh is not None:
            return None  # device_put target resolved per-leaf by rules
        devs = jax.devices()
        if len(devs) == 1:
            # single-device host: a committed sharding would defeat the
            # warmup's jit cache (committed args lower differently than
            # the uncommitted seed tree, forcing a recompile inside the
            # first measured step); ownership comes from the explicit
            # copies, so no placement pin is needed
            return None
        return jax.sharding.SingleDeviceSharding(devs[wid % len(devs)])

    # -- Table-2 transports ----------------------------------------------------
    def provision(self, wid: int, strategy: str,
                  donor: Optional[int] = None) -> tuple[Any, float]:
        """Materialize replica ``wid``'s own params tree through
        ``strategy``; returns ``(params, measured_seconds)``.

        The measured wall time is reported to the TLManager so the
        Scaler's next cost query predicts from observed bandwidth.
        """
        if strategy not in STRATEGIES:
            raise ValueError(f"unknown weight strategy {strategy!r}")
        if wid in self._owned:
            raise ValueError(f"replica {wid} already owns a params tree")
        sh = self.placement(wid)
        t0 = time.perf_counter()
        if strategy == "d2d":
            if donor is None or donor not in self._owned:
                raise ValueError(
                    f"d2d provisioning for replica {wid} needs a live "
                    f"donor (have {sorted(self._owned)}, got {donor!r}); "
                    f"scale-from-zero must fall back to 'disk'"
                )
            src = self._owned[donor]

            def pull(x):
                x = jnp.asarray(x)
                if sh is not None and x.devices() != sh.device_set:
                    return jax.device_put(x, sh)  # true cross-device
                # same device: on-device copy — owned buffers, no alias
                return jnp.copy(x)

            params = jax.tree.map(pull, src)
        elif strategy == "cpu":
            params = jax.tree.map(lambda h: _owned_put(h, sh), self.host)
        else:  # disk
            shardings = (None if sh is None
                         else jax.tree.map(lambda _: sh, self.host))
            params, _ = load_checkpoint(
                self.ckpt_dir, 0, self.host, shardings=shardings
            )
        params = jax.block_until_ready(params)
        dt = time.perf_counter() - t0
        self._owned[wid] = params
        if self.tl is not None:
            self.tl.observe_weight_load(strategy, self.nbytes, dt)
        return params, dt
