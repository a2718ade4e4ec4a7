"""Accelerator (TPU-first) detection and resource shaping.

Counterpart of the reference's pluggable accelerator managers
(reference: python/ray/_private/accelerators/tpu.py:71) but TPU is the
*primary* accelerator here, not an afterthought: a node contributes

  - ``TPU``: chips on this host,
  - ``TPU-<pod_type>-head``: 1 on the host that is rank 0 of its pod slice
    (reference: tpu.py:362-381 — lets exactly one task/actor gang-schedule a
    whole slice),
  - node labels ``rtpu.io/pod-type``, ``rtpu.io/slice-name``,
    ``rtpu.io/worker-id`` describing ICI topology for slice-aware placement.

Detection deliberately avoids importing jax (that would initialize the TPU
runtime inside control-plane processes); it reads device files and TPU-VM
environment metadata only.

One process per chip: a chip belongs to the first process that initializes
the TPU runtime on it, so the raylet hands every lease an env that names the
chips it owns (`visible_chip_env`) or keeps it off them (`hidden_chip_env`).
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Tuple


def num_tpu_chips() -> int:
    env = os.environ.get("RTPU_num_tpu_chips")
    if env is not None:
        return int(env)
    # TPU VMs expose one /dev/accel* per chip; hosts that pass the chips
    # through VFIO (the v5e machines this repo runs on) expose one numbered
    # group per attached chip beside the /dev/vfio/vfio control node. The
    # PCI bus is no guide there: a one-chip machine still lists four devices.
    return len(glob.glob("/dev/accel*")) or len(glob.glob("/dev/vfio/[0-9]*"))


def tpu_pod_type() -> str:
    """E.g. 'v5litepod-8'; empty when the host does not say."""
    return (
        os.environ.get("RTPU_tpu_pod_type")
        or os.environ.get("TPU_ACCELERATOR_TYPE", "").lower()
    )


def tpu_worker_id() -> int:
    return int(os.environ.get("TPU_WORKER_ID", "0"))


def tpu_slice_name() -> str:
    return os.environ.get("TPU_NAME", os.environ.get("HOSTNAME", "local-slice"))


def node_resources_and_labels() -> Tuple[Dict[str, float], Dict[str, str]]:
    resources: Dict[str, float] = {}
    labels: Dict[str, str] = {}
    chips = num_tpu_chips()
    if chips > 0:
        resources["TPU"] = float(chips)
        pod = tpu_pod_type()
        if pod:
            labels["rtpu.io/pod-type"] = pod
            labels["rtpu.io/slice-name"] = tpu_slice_name()
            labels["rtpu.io/worker-id"] = str(tpu_worker_id())
            if tpu_worker_id() == 0:
                # One slice-head resource per pod slice; scheduling one task on
                # it is how a whole-slice SPMD job gang-launches.
                resources[f"TPU-{pod.upper()}-head"] = 1.0
    return resources, labels


# The (x, y, z) grid one process drives, by chip count. Each was brought up
# with libtpu 0.0.34 on a four-chip v5e host (2x2); see CHANGES.md, PR 22.
_PROCESS_BOUNDS_BY_CHIPS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}


def visible_chip_env(chip_ids) -> Dict[str, str]:
    """Env vars limiting a worker to specific chips (reference: TPU_VISIBLE_CHIPS).
    A count with no known grid names its chips and lets libtpu lay them out."""
    ids = [int(c) for c in chip_ids]
    env = {"TPU_VISIBLE_CHIPS": ",".join(map(str, ids))}
    bounds = _PROCESS_BOUNDS_BY_CHIPS.get(len(ids))
    if bounds:
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return env


def hidden_chip_env() -> Dict[str, str]:
    """Env vars under which jax cannot initialize the TPU runtime: for a
    zero-TPU lease on a node that has chips. An empty TPU_VISIBLE_CHIPS does
    not do it (libtpu 0.0.34 still takes the chip); the platform list does."""
    return {"JAX_PLATFORMS": "cpu"}
