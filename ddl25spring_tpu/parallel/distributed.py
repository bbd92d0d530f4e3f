"""Multi-host distributed runtime: the framework's gloo/MPI replacement.

The reference's distributed backend is torch.distributed over gloo with
localhost rendezvous via MASTER_ADDR/MASTER_PORT env vars and one OS process
per rank (reference: lab/tutorial_1b/DP/gradient_aggr/intro_DP_GA.py:11-15;
SURVEY.md §2.11). The TPU-native equivalent is one JAX process per HOST (not
per device): `jax.distributed.initialize` performs the rendezvous, after
which `jax.devices()` spans every chip in the slice/pod and the SAME
single-program mesh code runs unchanged — collectives ride ICI within a
slice and DCN between hosts. No ranks in user code, no sockets, no tags.

`hybrid_mesh` builds the two-tier topology explicitly: DCN-connected axes
(across hosts — put data parallelism here; it communicates once per step)
outer, ICI-connected axes (within a slice — model/stage/seq/expert axes,
which communicate per layer) inner.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from .mesh import AXES


def _is_initialized() -> bool:
    return bool(jax.distributed.is_initialized())


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Multi-host rendezvous — the `init_process_group` analog.

    With no arguments, reads the standard env vars (JAX_COORDINATOR_ADDRESS
    etc.) or the TPU metadata server, mirroring the reference's
    MASTER_ADDR/MASTER_PORT convention (intro_DP_GA.py:12-14) without
    per-rank processes. Safe to call on single-host (no-op there).

    MUST run before anything touches the XLA backend — so this guard checks
    only is_initialized() and the env vars; calling e.g. jax.process_count()
    here would itself initialize the backend and make the rendezvous
    impossible.
    """
    if _is_initialized():
        return
    kw = {}
    if coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS"):
        kw["coordinator_address"] = (coordinator_address or
                                     os.environ["JAX_COORDINATOR_ADDRESS"])
    if num_processes or os.environ.get("JAX_NUM_PROCESSES"):
        kw["num_processes"] = int(num_processes or
                                  os.environ["JAX_NUM_PROCESSES"])
    if process_id is not None or os.environ.get("JAX_PROCESS_ID"):
        kw["process_id"] = int(process_id if process_id is not None
                               else os.environ["JAX_PROCESS_ID"])
    if not kw:
        return  # single-host, nothing to rendezvous
    jax.distributed.initialize(**kw)


def hybrid_mesh(ici_axes: Dict[str, int], dcn_axes: Dict[str, int],
                *, devices: Optional[Sequence] = None) -> Mesh:
    """Two-tier mesh: ``dcn_axes`` split across hosts (slow, once-per-step
    collectives — data parallelism), ``ici_axes`` within each host/slice
    (fast, per-layer collectives — model/stage/seq/expert).

    Axis ordering in the result follows mesh.AXES so the train-step factories
    (dp/pp/tp/sp/ep) work unchanged on the hybrid mesh.
    """
    from jax.experimental import mesh_utils

    dcn_names = [a for a in AXES if a in dcn_axes] + \
                [a for a in dcn_axes if a not in AXES]
    ici_names = [a for a in AXES if a in ici_axes] + \
                [a for a in ici_axes if a not in AXES]
    overlap = set(dcn_names) & set(ici_names)
    assert not overlap, f"axes cannot span both tiers: {overlap}"

    if devices is None and jax.process_count() > 1:
        # create_hybrid_device_mesh wants same-rank shapes composed
        # elementwise; our tiers are disjoint, so pad each with 1s — the
        # elementwise product is then exactly [*dcn_shape, *ici_shape].
        dev_array = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=[1] * len(dcn_names) + [ici_axes[a] for a in ici_names],
            dcn_mesh_shape=[dcn_axes[a] for a in dcn_names] + [1] * len(ici_names),
        )
    else:
        devices = list(devices if devices is not None else jax.devices())
        shape = [dcn_axes[a] for a in dcn_names] + \
                [ici_axes[a] for a in ici_names]
        need = int(np.prod(shape))
        assert need <= len(devices), (shape, len(devices))
        dev_array = np.asarray(devices[:need]).reshape(shape)

    names = tuple(dcn_names + ici_names)
    # Reorder to canonical AXES order for train-step factory compatibility.
    order = sorted(range(len(names)),
                   key=lambda i: (AXES.index(names[i])
                                  if names[i] in AXES else len(AXES)))
    dev_array = np.transpose(np.asarray(dev_array), order)
    return Mesh(dev_array, tuple(names[i] for i in order))


def hier_data_mesh(islands: int, island_size: int, *,
                   devices: Optional[Sequence] = None) -> Mesh:
    """Two-tier DATA-parallel mesh: ``islands`` ICI islands of
    ``island_size`` replicas each, bridged by DCN — axes ``("dcn",
    "data")`` with island-major device order (replica (d, s) = device
    d·island_size + s). This is the substrate of the hierarchical
    collectives (parallel/compress.py): full-precision reduction inside
    each island's ``data`` axis, a compressed exchange across ``dcn``
    only — wire compression spent exactly where bandwidth is scarce.

    Multi-host: delegates to ``hybrid_mesh`` so the ``dcn`` axis really
    spans hosts (``create_hybrid_device_mesh``). Single-process (the CPU
    test mesh): the first islands·island_size devices, island-major —
    the SAME logical topology, so every factorization is testable on the
    virtual mesh."""
    return hybrid_mesh({"data": island_size}, {"dcn": islands},
                       devices=devices)


def process_info() -> Dict[str, int]:
    """Host-level identity (the replacement for the reference's rank arg)."""
    return {
        "process_id": jax.process_index(),
        "num_processes": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
    }
