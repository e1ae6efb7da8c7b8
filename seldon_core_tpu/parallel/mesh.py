"""Mesh construction helpers — single-chip through multi-host.

Multi-host model (SURVEY §5 "distributed communication backend"): the
reference's NCCL/MPI analogue is the JAX runtime itself — every host
runs the same program, ``initialize_distributed()`` wires the hosts into
one runtime (GCE metadata autodetect on TPU pods, explicit
coordinator/process env elsewhere), and ``jax.devices()`` then spans the
pod. Collectives ride ICI inside a slice and DCN between slices; the
mesh-building helpers put DCN-crossing axes (data, stage) on the outer
dimensions so tp/sp traffic never leaves a slice
(``make_hybrid_mesh``)."""

from __future__ import annotations

import os
from typing import Dict, Optional, Sequence


class MeshShapeError(ValueError):
    """A mesh shape that cannot be built or cannot shard the model.

    Raised by :func:`make_mesh` / :func:`parse_mesh_shape` /
    :func:`validate_model_dims` instead of letting XLA fail later with an
    opaque reshape/partition error. Subclasses ``ValueError`` so existing
    ``except ValueError`` admission paths keep refusing bad shapes."""


def factor_devices(n: int) -> Dict[str, int]:
    """Factor n devices into (data, stage, seq, model) prioritising: tp,
    then pp, then dp, then sp. All five strategies stay *wired* at any n
    (expert parallelism rides data x seq); axes degrade to 1 when chips run
    out. 8 chips -> {data:2, stage:2, seq:1, model:2}; 16 -> all 2;
    32 -> model 4.
    """
    if not isinstance(n, int) or n < 1:
        raise MeshShapeError(f"cannot factor {n!r} devices: need a positive int")
    axes = {"data": 1, "stage": 1, "seq": 1, "model": 1}
    order = ["model", "stage", "data", "seq"]
    i = 0
    while n > 1:
        axis = order[i % len(order)]
        if n % 2 == 0:
            axes[axis] *= 2
            n //= 2
        else:  # odd remainder goes to data
            axes["data"] *= n
            n = 1
        i += 1
    return axes


def make_mesh(shape: Dict[str, int], devices=None):
    """Build a Mesh with named axes from {axis: size}.

    Axis order follows the dict order; callers should put the slowest-
    varying (DCN-adjacent) axis first so ICI carries tp/sp collectives.
    """
    import jax
    import numpy as np

    if devices is None:
        devices = jax.devices()
    total = 1
    for ax, s in shape.items():
        if not isinstance(s, int) or s < 1:
            raise MeshShapeError(
                f"mesh axis {ax!r}={s!r}: sizes must be positive ints"
            )
        total *= s
    if total > len(devices):
        raise MeshShapeError(
            f"mesh {shape} needs {total} devices, have {len(devices)}"
        )
    if len(devices) % total != 0:
        # a non-dividing shape would silently strand the remainder chips
        # outside the mesh while XLA still sees them via jax.devices() —
        # surface the mistake here with the arithmetic spelled out
        raise MeshShapeError(
            f"mesh {shape} covers {total} of {len(devices)} devices; "
            f"{total} does not divide {len(devices)} — the leftover "
            f"{len(devices) % total} chip(s) would idle"
        )
    arr = np.asarray(devices[:total]).reshape(tuple(shape.values()))
    return jax.sharding.Mesh(arr, tuple(shape.keys()))


def parse_mesh_shape(raw: str) -> Dict[str, int]:
    """Parse ``"data=2,model=4"`` into an ordered ``{axis: size}`` dict.

    Strict by design — this is the admission-time parser behind the
    ``seldon.io/mesh`` annotation and the ``mesh_shape`` server knob, so
    every malformed input gets a typed :class:`MeshShapeError` naming the
    offending fragment instead of an opaque downstream failure. Accepted
    axis names are the house mesh axes (data/stage/seq/model); duplicate
    axes and non-positive sizes are refused."""
    if not isinstance(raw, str) or not raw.strip():
        raise MeshShapeError(f"mesh shape {raw!r}: expected 'axis=N,axis=N'")
    allowed = ("data", "stage", "seq", "model")
    shape: Dict[str, int] = {}
    for part in raw.split(","):
        part = part.strip()
        if not part:
            raise MeshShapeError(f"mesh shape {raw!r}: empty segment")
        if "=" not in part:
            raise MeshShapeError(
                f"mesh shape segment {part!r}: expected 'axis=N'"
            )
        ax, _, val = part.partition("=")
        ax = ax.strip()
        if ax not in allowed:
            raise MeshShapeError(
                f"mesh axis {ax!r}: must be one of {allowed}"
            )
        if ax in shape:
            raise MeshShapeError(f"mesh axis {ax!r} given twice in {raw!r}")
        try:
            size = int(val.strip())
        except ValueError:
            raise MeshShapeError(
                f"mesh axis {ax!r}={val.strip()!r}: size must be an int"
            ) from None
        if size < 1:
            raise MeshShapeError(
                f"mesh axis {ax!r}={size}: sizes must be positive"
            )
        shape[ax] = size
    return shape


def validate_model_dims(
    shape: Dict[str, int],
    n_heads: int,
    d_ff: int,
    n_kv_heads: Optional[int] = None,
) -> None:
    """Reject a mesh whose ``model`` axis cannot shard the hard-split
    dims. Attention heads and the FFN hidden dim are partitioned (not
    replicated) under the TP layout, so ``model`` must divide both —
    otherwise XLA fails deep inside the first sharded dispatch with an
    unactionable partition error. KV heads are allowed to be indivisible
    (GQA targets / thin drafts): the cache layer replicates them instead,
    so that is NOT an error here."""
    tp = int(shape.get("model", 1))
    if tp <= 1:
        return
    if n_heads % tp != 0:
        raise MeshShapeError(
            f"mesh model={tp} does not divide n_heads={n_heads}; "
            "attention heads are hard-sharded over the model axis"
        )
    if d_ff % tp != 0:
        raise MeshShapeError(
            f"mesh model={tp} does not divide d_ff={d_ff}; "
            "the FFN hidden dim is hard-sharded over the model axis"
        )
    del n_kv_heads  # indivisible KV heads replicate — see cache_sharding


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Join this host into a multi-host JAX runtime.

    On TPU pods ``jax.distributed.initialize()`` autodetects everything
    from the metadata server; elsewhere pass the coordinator explicitly
    or set ``SELDON_TPU_COORDINATOR`` / ``SELDON_TPU_NUM_PROCESSES`` /
    ``SELDON_TPU_PROCESS_ID``. Idempotent: returns False when the
    runtime is already initialized or when running single-process with
    no coordinator configured (the common dev/test case).
    """
    import jax

    coordinator_address = coordinator_address or os.environ.get(
        "SELDON_TPU_COORDINATOR"
    )
    if num_processes is None and "SELDON_TPU_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["SELDON_TPU_NUM_PROCESSES"])
    if process_id is None and "SELDON_TPU_PROCESS_ID" in os.environ:
        process_id = int(os.environ["SELDON_TPU_PROCESS_ID"])
    # decide the pod case from env alone — touching jax.default_backend()
    # here would initialize the XLA backends, after which
    # jax.distributed.initialize() refuses to run at all. A single-entry
    # TPU_WORKER_HOSTNAMES (e.g. "localhost" on a one-host slice) is not
    # a pod.
    workers = [
        w for w in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",") if w
    ]
    on_tpu_pod = len(workers) > 1 or bool(
        os.environ.get("MEGASCALE_COORDINATOR_ADDRESS")
    )
    if coordinator_address is None and not on_tpu_pod:
        return False
    if jax.distributed.is_initialized():
        return False
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        return True
    except RuntimeError as e:
        msg = str(e).lower()
        if "already" in msg or "only be called once" in msg:
            # raced another initializer — the documented idempotent no-op
            return False
        if "must be called before" in msg:
            # distributed init was WANTED (coordinator/pod detected) but
            # something touched the XLA backends first: this host now runs
            # single-process and cross-host collectives will never form.
            # Loud warning instead of raise — serving a slice beats
            # crashing, but the operator must see it.
            import logging

            logging.getLogger(__name__).warning(
                "initialize_distributed: too late — XLA backends already "
                "initialized before the multi-host join (%s). This process "
                "continues SINGLE-HOST; call initialize_distributed() "
                "before any jax API use to form the pod.", e,
            )
            return False
        raise


def make_hybrid_mesh(
    ici_shape: Dict[str, int],
    dcn_shape: Optional[Dict[str, int]] = None,
    devices=None,
):
    """Mesh spanning slices/hosts: ``dcn_shape`` axes (typically data
    and/or stage — gradient/activation hops that tolerate DCN latency)
    partition BETWEEN slices, ``ici_shape`` axes (model/seq — latency-
    critical tp/sp collectives) partition WITHIN a slice.

    Falls back to a flat :func:`make_mesh` when there is a single slice
    (or no slice topology, e.g. the CPU test mesh) — same axis names, so
    callers never branch.
    """
    import jax

    if devices is None:
        devices = jax.devices()
    dcn_shape = dict(dcn_shape or {})
    n_slices = len({getattr(d, "slice_index", 0) for d in devices})
    dcn_total = 1
    for s in dcn_shape.values():
        dcn_total *= s
    if n_slices <= 1 or dcn_total <= 1:
        merged = {**dcn_shape, **ici_shape}
        for ax, size in dcn_shape.items():
            if ax in ici_shape:
                merged[ax] = ici_shape[ax] * size
        return make_mesh(merged, devices=devices)
    from jax.experimental import mesh_utils

    axis_names = list(dcn_shape.keys()) + [
        ax for ax in ici_shape if ax not in dcn_shape
    ]
    per_slice = [ici_shape.get(ax, 1) for ax in axis_names]
    across = [dcn_shape.get(ax, 1) for ax in axis_names]
    arr = mesh_utils.create_hybrid_device_mesh(
        per_slice, across, devices=devices
    )
    return jax.sharding.Mesh(arr, tuple(axis_names))
