"""Host parsing and slot assignment.

Parity: ``horovod/runner/common/util/hosts.py`` — ``parse_hosts`` (``:54``)
and ``get_host_assignments`` (``:100``), which turn ``host1:4,host2:4``
into per-process ``SlotInfo(rank, local_rank, cross_rank, size,
local_size, cross_size)``.

On TPU the "slots" of a host are its chips; rank numbering is
host-major exactly like the reference (so ``local`` is intra-host ICI and
``cross`` is DCN — the hierarchy the collectives exploit). For pod slices
discovered from the TPU environment (rather than an explicit ``-H`` list),
see :func:`discover_tpu_hosts`.
"""

from __future__ import annotations

import dataclasses
import glob
import math
import os
from typing import List, Optional


@dataclasses.dataclass
class HostInfo:
    hostname: str
    slots: int

    @staticmethod
    def from_string(spec: str) -> "HostInfo":
        spec = spec.strip()
        if ":" in spec:
            host, slots = spec.rsplit(":", 1)
            return HostInfo(host, int(slots))
        return HostInfo(spec, 1)


@dataclasses.dataclass
class SlotInfo:
    hostname: str
    rank: int
    local_rank: int
    cross_rank: int
    size: int
    local_size: int
    cross_size: int

    def to_response_string(self) -> str:
        return ":".join(
            str(x)
            for x in (
                self.rank, self.local_rank, self.cross_rank,
                self.size, self.local_size, self.cross_size,
            )
        )


def parse_hosts(hosts_string: str) -> List[HostInfo]:
    """``"a:4,b:4"`` → HostInfo list (reference ``hosts.py:54``)."""
    return [HostInfo.from_string(s) for s in hosts_string.split(",") if s.strip()]


def get_host_assignments(
    hosts: List[HostInfo], min_np: int, max_np: Optional[int] = None
) -> List[SlotInfo]:
    """Assign global/local/cross ranks host-major.

    Mirrors the reference's assignment semantics (``hosts.py:100``):
    ranks are dense host-by-host; ``cross_rank`` is the host index among
    hosts that own the same local slot; raises when fewer than ``min_np``
    total slots exist; caps at ``max_np`` when given.
    """
    total = sum(h.slots for h in hosts)
    if total < min_np:
        raise ValueError(
            f"requested at least {min_np} processes but hosts provide {total}"
        )
    np_ = min(total, max_np) if max_np else total

    assignments: List[SlotInfo] = []
    rank = 0
    for h in hosts:
        for local_rank in range(h.slots):
            if rank >= np_:
                break
            assignments.append(
                SlotInfo(
                    hostname=h.hostname,
                    rank=rank,
                    local_rank=local_rank,
                    cross_rank=0,  # filled below
                    size=np_,
                    local_size=min(h.slots, np_ - (rank - local_rank)),
                    cross_size=0,  # filled below
                )
            )
            rank += 1

    # cross rank/size: computed among the hosts that actually own this
    # local slot index (reference hosts.py:127-142) — with heterogeneous
    # slot counts the absolute host index would exceed cross_size.
    by_local: dict = {}
    for slot in assignments:
        by_local.setdefault(slot.local_rank, []).append(slot)
    for slots_for_local in by_local.values():
        for i, slot in enumerate(slots_for_local):
            slot.cross_rank = i
            slot.cross_size = len(slots_for_local)
    return assignments


def _chips_from_bounds(bounds: str) -> int:
    return math.prod(int(d) for d in bounds.split(","))  # e.g. "2,2,1"


def _local_chip_count() -> int:
    """Chips attached to this host, counted WITHOUT initialising a JAX
    backend: the launcher is the parent of the worker it spawns, a chip
    belongs to one process at a time, and a parent that has asked JAX for
    its devices holds the chip the worker needs. The kernel's device nodes
    win (``/dev/accel<N>``, or one ``/dev/vfio/<N>`` group per chip): the
    TPU runtime's ``TPU_CHIPS_PER_HOST_BOUNDS`` describes the whole host
    even where this machine was handed one chip of it. 0 when neither is
    there."""
    nodes = len(glob.glob("/dev/accel[0-9]*")) or len(
        glob.glob("/dev/vfio/[0-9]*")
    )
    if nodes:
        return nodes
    bounds = os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS", "")
    return _chips_from_bounds(bounds) if bounds else 0


def discover_tpu_hosts(default_slots: Optional[int] = None) -> List[HostInfo]:
    """Derive the host list from the TPU pod-slice environment.

    Replaces the reference's ssh/NIC discovery probe
    (``horovod/runner/driver/driver_service.py:122-257``): on Cloud TPU the
    topology is published in env vars / the metadata-derived
    ``TPU_WORKER_HOSTNAMES`` list, and each worker's chip count in
    ``TPU_CHIPS_PER_HOST_BOUNDS``. A slice of one host (or no hostname
    list) is one local process driving the chips :func:`_local_chip_count`
    finds; on a host where it finds none, ``default_slots`` (the
    launcher's ``-np``) or an error naming the arguments that settle it.
    JAX is never asked.
    """
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    names = [h.strip() for h in hostnames.split(",") if h.strip()]
    if len(names) > 1:
        bounds = os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS", "")
        chips = _chips_from_bounds(bounds) if bounds else 4
        return [HostInfo(n, chips) for n in names]
    slots = _local_chip_count() or default_slots
    if not slots:
        raise ValueError(
            "cannot tell how many chips this host has (no TPU_* "
            "environment, no /dev/accel* or /dev/vfio/* device nodes); "
            "pass -H/--hostfile or -np"
        )
    return [HostInfo(names[0] if names else "localhost", slots)]
