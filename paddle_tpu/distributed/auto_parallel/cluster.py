"""Cluster description (reference: python/paddle/distributed/
auto_parallel/static/cluster.py — Device/Machine/Cluster with link
bandwidths driving the cost model).

TPU-native: the cluster is a TPU slice — chips with known peak FLOPs /
HBM bandwidth, ICI links inside the slice, DCN across slices. Built
automatically from jax.devices() or explicitly for what-if planning.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from ...device.peaks import CHIP_PEAKS, ChipPeaks, chip_name

__all__ = ["DeviceSpec", "LinkSpec", "Machine", "Cluster",
           "build_cluster"]

# planning placeholder for host-device dry runs of the cost model: NOT a
# published peak, and never a divisor of a reported utilization
_CPU_PLANNING = ChipPeaks(0.5e12, 8e9, 50e9, 10e9)


class DeviceSpec:
    """reference: cluster.py Device."""

    def __init__(self, global_id, local_id, machine_id, dtype="TPU",
                 model="v5e"):
        self.global_id = global_id
        self.local_id = local_id
        self.machine_id = machine_id
        self.type = dtype
        self.model = model
        # an unknown chip is a KeyError, not a v5e
        pk = _CPU_PLANNING if model == "cpu" else CHIP_PEAKS[model]
        self.peak_tflops = pk.bf16_flops / 1e12
        self.memory_gb = pk.hbm_bytes / 1e9
        self.hbm_gbps = pk.hbm_bytes_per_s / 1e9
        self.ici_gbps = pk.ici_link_bytes_per_s / 1e9


class LinkSpec:
    """reference: cluster.py Link."""

    def __init__(self, source, target, kind="ICI", bandwidth_gbps=50.0,
                 latency_us=1.0):
        self.source = source
        self.target = target
        self.type = kind
        self.bandwidth_gbps = bandwidth_gbps
        self.latency_us = latency_us


class Machine:
    """reference: cluster.py Machine — one host with its chips."""

    def __init__(self, machine_id):
        self.id = machine_id
        self.devices: Dict[int, DeviceSpec] = {}

    def add_device(self, dev: DeviceSpec):
        self.devices[dev.global_id] = dev


class Cluster:
    """reference: cluster.py Cluster."""

    def __init__(self):
        self.machines: Dict[int, Machine] = {}
        self.links: List[LinkSpec] = []

    def add_machine(self, m: Machine):
        self.machines[m.id] = m

    def add_link(self, link: LinkSpec):
        self.links.append(link)

    @property
    def devices(self) -> List[DeviceSpec]:
        out = []
        for m in self.machines.values():
            out.extend(m.devices.values())
        return sorted(out, key=lambda d: d.global_id)

    def device(self, global_id) -> DeviceSpec:
        for m in self.machines.values():
            if global_id in m.devices:
                return m.devices[global_id]
        raise KeyError(global_id)

    def bandwidth_gbps(self, a: int, b: int) -> float:
        """Effective link bandwidth between two devices: ICI inside a
        machine/slice, DCN across."""
        da, db = self.device(a), self.device(b)
        if da.machine_id == db.machine_id:
            return da.ici_gbps
        dcn = [l for l in self.links if l.type == "DCN"]
        return dcn[0].bandwidth_gbps if dcn else 12.5  # ~100 Gb/s default

    # ------------------------------------------------------------- build
    @staticmethod
    def from_devices(n_devices, chips_per_host=4, model="v5e",
                     dcn_gbps=12.5):
        c = Cluster()
        for g in range(n_devices):
            mid = g // chips_per_host
            if mid not in c.machines:
                c.add_machine(Machine(mid))
            c.machines[mid].add_device(
                DeviceSpec(g, g % chips_per_host, mid, model=model))
        n_machines = len(c.machines)
        if n_machines > 1:
            c.add_link(LinkSpec(0, chips_per_host, kind="DCN",
                                bandwidth_gbps=dcn_gbps))
        return c


def build_cluster(model: Optional[str] = None) -> Cluster:
    """Auto-describe the current jax environment as a Cluster."""
    import jax

    devs = jax.devices()
    kind = model
    if kind is None:
        kind = "cpu" if devs[0].platform == "cpu" else chip_name(devs[0])
    per_host = max(1, len([d for d in devs
                           if d.process_index == devs[0].process_index]))
    return Cluster.from_devices(len(devs), chips_per_host=per_host,
                                model=kind)
