"""TPU pod topology: where this host sits, and link classification.

This is the TPU-native replacement for the reference's IDC/location string
affinity (``scheduler/scheduling/evaluator/evaluator_base.go`` scores IDC and
location by string match). Here hosts carry real fabric coordinates: slice
name + ICI chip coords + zone, and the scheduler computes a ``LinkType``
(LOCAL > ICI > DCN > WAN) plus an ICI hop distance for parent scoring.
"""

from __future__ import annotations

import dataclasses
import functools
import os

from ..idl.messages import LinkType, TopologyInfo


@functools.lru_cache(maxsize=1)
def detect() -> TopologyInfo:
    """This host's pod position, from what the environment gives.

    Never touches JAX: a chip belongs to one process, and most daemons on
    a TPU host (and every scheduler) must come up without taking it. The
    TPU runtime env names the slice and the worker; ``DF_ICI_COORDS``
    injects chip coordinates for fake-pod harnesses and deployments that
    know them. The process that does open a device sink adds what only
    the devices can tell (``with_devices``). With nothing set the host is
    a plain DCN peer.
    """
    slice_name = os.environ.get("TPU_SLICE_NAME", "")
    pod = os.environ.get("DF_POD_ID", "")
    zone = os.environ.get("DF_ZONE", os.environ.get("CLOUD_ZONE", ""))
    try:
        worker = int(os.environ.get("TPU_WORKER_ID", "-1"))
    except ValueError:
        worker = -1
    coords = None
    # malformed values degrade to None: a typo must not kill daemon startup
    coords_env = os.environ.get("DF_ICI_COORDS", "")
    if coords_env:
        try:
            coords = tuple(int(x) for x in coords_env.split(","))
        except ValueError:
            coords = None
    if not zone:
        zone = os.environ.get("DF_DEFAULT_ZONE", "local")
    return TopologyInfo(slice_name=slice_name, worker_index=worker,
                        ici_coords=coords, num_chips=0, zone=zone, pod=pod)


def with_devices(base: TopologyInfo, devices: list) -> TopologyInfo:
    """``base`` plus what this host's devices tell, for the process that
    has JAX up: chip count, the first chip's mesh coordinates, the worker
    index. Injected coordinates win over detected ones. The slice name is
    NOT derived from the device kind — "TPU v5 lite-1" would put every
    one-chip host of a fleet into one ICI domain; without
    ``TPU_SLICE_NAME`` the host stays a DCN peer that happens to hold
    chips."""
    chips = [d for d in devices if d.platform == "tpu"]
    if not chips:
        return base
    first = chips[0]
    coords = base.ici_coords
    if coords is None:
        coords = tuple(getattr(first, "coords", ()) or ()) or None
    worker = base.worker_index
    if worker < 0:
        worker = getattr(first, "process_index", 0)
    return dataclasses.replace(base, num_chips=len(chips),
                               ici_coords=coords, worker_index=worker)


def pod_id(t: TopologyInfo | None) -> str:
    """The host's pod identity: the ICI bandwidth domain it belongs to.

    An explicit ``pod`` (``DF_POD_ID``, deployments that group hosts
    across slice boundaries) wins; otherwise the pod is derived from
    slice identity — one slice == one ICI domain == one pod. "" means no
    pod identity at all (the plain-DCN-peer fallback ``detect()``
    degrades to on non-TPU hosts): such a host belongs to no pod and the
    federation plane never restricts it. Stable across re-announce by
    construction — a pure function of the announced coordinates, never
    of announce order or time."""
    if t is None:
        return ""
    return t.pod or t.slice_name


def same_pod(a: TopologyInfo | None, b: TopologyInfo | None) -> bool:
    pa, pb = pod_id(a), pod_id(b)
    return bool(pa) and pa == pb


def link_type(a: TopologyInfo | None, b: TopologyInfo | None,
              *, same_host: bool = False) -> LinkType:
    """Classify the best link between two hosts' positions."""
    if same_host:
        return LinkType.LOCAL
    if a is None or b is None:
        return LinkType.WAN
    if a.slice_name and a.slice_name == b.slice_name:
        return LinkType.ICI
    if a.zone and a.zone == b.zone:
        return LinkType.DCN
    return LinkType.WAN


class LinkClass:
    """One classified (child, parent) pair: the link tier plus the pod/
    DCN coordinates the federation plane routes by. ``dcn_hops`` is the
    DCN distance between the two PODS: 0 = same pod (bytes stay on the
    wired ICI mesh), 1 = pod-crossing inside one zone (the DCN tier
    cross-pod federation exists to ration), 2 = cross-zone / unknown
    (WAN). ``ici`` is the chip-mesh Manhattan distance, meaningful only
    when ``link`` is ICI."""

    __slots__ = ("link", "same_pod", "dcn_hops", "ici")

    def __init__(self, link: LinkType, same_pod_: bool, dcn_hops: int,
                 ici: int):
        self.link = link
        self.same_pod = same_pod_
        self.dcn_hops = dcn_hops
        self.ici = ici


def classify(a: TopologyInfo | None, b: TopologyInfo | None,
             *, same_host: bool = False) -> LinkClass:
    """``link_type`` plus the pod tier: where the bytes would flow AND
    whether they would leave the pod. A host with no topology at all
    classifies as a plain WAN peer with no pod (the ``detect()``
    fallback) — cross-pod routing never restricts it, it just scores
    like the distant peer it is."""
    lt = link_type(a, b, same_host=same_host)
    sp = same_host or same_pod(a, b)
    if sp:
        dcn = 0
    elif lt in (LinkType.LOCAL, LinkType.ICI, LinkType.DCN):
        dcn = 1
    else:
        dcn = 2
    hops = ici_hops(a, b) if a is not None and b is not None else 1 << 16
    return LinkClass(lt, sp, dcn, hops)


def ici_hops(a: TopologyInfo, b: TopologyInfo) -> int:
    """Manhattan distance in the chip mesh; large when unknown.

    On a v5p torus each hop adds latency but per-hop bandwidth stays high;
    the evaluator uses this only to break ties between same-slice parents.
    """
    if not a.ici_coords or not b.ici_coords or len(a.ici_coords) != len(b.ici_coords):
        return 1 << 16
    return int(sum(abs(int(x) - int(y)) for x, y in zip(a.ici_coords, b.ici_coords)))


# relative bandwidth expectations per link class, used by evaluator scoring:
# ICI on v5p is ~4.8 TB/s/chip-neighborhood vs ~100-400 Gbps DCN NICs.
LINK_BANDWIDTH_SCORE = {
    LinkType.LOCAL: 1.0,
    LinkType.ICI: 0.9,
    LinkType.DCN: 0.4,
    LinkType.WAN: 0.1,
}

# The pinned link-tier vocabulary: the name each LinkType rides the
# decision ledger under (candidate ``link_tier`` — docs/OBSERVABILITY.md
# decision-row schema). Pinned like EXCLUSION_REASONS: replaying
# federation fairness offline needs the tier strings stable across
# versions, and the ordering here (best to worst) must agree with
# LINK_BANDWIDTH_SCORE (descending) and the dispatcher's LINK_TIER
# (ascending) — unit-pinned in tests/test_federation.py.
LINK_TIER_NAMES = {
    LinkType.LOCAL: "local",
    LinkType.ICI: "ici",
    LinkType.DCN: "dcn",
    LinkType.WAN: "wan",
}
