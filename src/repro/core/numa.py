"""NUMA topology descriptors for disaggregated accelerators.

The paper targets AMD MI300X (8 XCD chiplets, each with a private 4 MB L2).
We model any machine whose compute is partitioned into *domains*, each with a
private cache and a set of concurrent execution slots (CUs on a GPU chiplet,
TensorCores on a TPU chip, chips in a TPU pod when the "cache" is HBM).

The same descriptor drives three layers of the system:
  * the cache simulator (``core.cache_sim``) replaying paper configurations,
  * the Pallas kernel grid scheduler (``kernels.flash_attention``) where
    ``num_domains`` is the number of TensorCores sharing HBM (megacore),
  * the mesh-level placement (``core.placement``) where a TPU pod is treated
    as a NUMA machine with one domain per chip.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Topology:
    """A NUMA-ish accelerator: ``num_domains`` domains, private caches.

    Attributes:
      name: human-readable identifier.
      num_domains: number of NUMA domains (XCDs / TensorCores / chips).
      slots_per_domain: concurrent workgroup slots per domain (CUs on an XCD;
        1 for a TPU TensorCore which executes its grid sequentially).
      cache_bytes: private cache capacity per domain (L2 on MI300X; the VMEM
        operand-residency budget on TPU).
      peak_flops: per-*device* peak bf16 FLOP/s (all domains combined).
      hbm_bw: per-device HBM bandwidth, bytes/s.
      link_bw: inter-domain / inter-chip link bandwidth, bytes/s (Infinity
        Fabric per-XCD share on MI300X; a single ICI link on TPU).
    """

    name: str
    num_domains: int
    slots_per_domain: int
    cache_bytes: int
    peak_flops: float
    hbm_bw: float
    link_bw: float

    @property
    def total_slots(self) -> int:
        return self.num_domains * self.slots_per_domain

    @property
    def flops_per_slot(self) -> float:
        return self.peak_flops / self.total_slots

    @property
    def hbm_bw_per_slot(self) -> float:
        return self.hbm_bw / self.total_slots


# --- Presets -----------------------------------------------------------------

#: The paper's evaluation platform (Table 1): 8 XCDs x 38 CUs, 4 MB L2/XCD,
#: 192 GB HBM3 @ 5.3 TB/s, ~1.3 PFLOP/s bf16 peak (MI300X datasheet).
MI300X = Topology(
    name="mi300x",
    num_domains=8,
    slots_per_domain=38,
    cache_bytes=4 * 1024 * 1024,
    peak_flops=1.307e15,
    hbm_bw=5.3e12,
    link_bw=0.75e12,  # per-XCD Infinity-Fabric share (estimate)
)

#: Target hardware for the TPU port. v5e: one TensorCore per chip, so the
#: intra-chip NUMA level is degenerate; the pod level (placement.py) carries
#: the paper's insight. Constants per the assignment brief.
TPU_V5E = Topology(
    name="tpu_v5e",
    num_domains=1,
    slots_per_domain=1,
    cache_bytes=128 * 1024 * 1024,  # VMEM per core
    peak_flops=197e12,
    hbm_bw=819e9,
    link_bw=50e9,  # per ICI link
)

#: v5p-like megacore: two TensorCores sharing one HBM. Pallas splits
#: ``parallel`` grid dimensions across the two cores — the direct analogue of
#: WG->XCD assignment, and the topology under which the swizzle arithmetic is
#: exercised on-chip.
TPU_V5P_MEGACORE = Topology(
    name="tpu_v5p_megacore",
    num_domains=2,
    slots_per_domain=1,
    cache_bytes=128 * 1024 * 1024,
    peak_flops=459e12,
    hbm_bw=2.765e12,
    link_bw=100e9,
)

#: What plans resolved on a CPU host (interpret-mode tests, dry-runs) are
#: priced against. It carries the v5p megacore figures so that those plans
#: exercise the two-domain swizzle arithmetic; it describes no device.
CPU_REHEARSAL = dataclasses.replace(TPU_V5P_MEGACORE, name="cpu_rehearsal")

#: Topology of each TPU ``jax.Device.device_kind`` the planner knows. A
#: TPU whose kind is missing here is an error, never a guess.
TPU_BY_DEVICE_KIND = {
    "TPU v5 lite": TPU_V5E,
    "TPU v5": TPU_V5P_MEGACORE,
}


@dataclasses.dataclass(frozen=True)
class MeshTopology:
    """Two-tier NUMA: ``num_devices`` chips (each a :class:`Topology` of
    intra-chip domains) joined by an inter-device fabric that is a second,
    slower bandwidth rung above each chip's HBM.

    This is the recursive form of the paper's hierarchy: head -> domain
    inside a chip, head group -> device across the mesh. ``perf_model``
    prices decode placement jointly over (domain, device) with it —
    device-local split-K ranges ride ``chip.hbm_bw`` while ranges that
    straddle devices pay ``device_link_bw`` for the crossing bytes.

    ``device_link_bw`` is the per-device share of the mesh interconnect in
    bytes/s. For TPU chips the preset ``Topology.link_bw`` already *is*
    the chip-to-chip ICI link, so it is the default; platforms whose
    ``link_bw`` means an intra-package fabric (MI300X) should pass the
    inter-GPU figure explicitly.
    """

    chip: Topology
    num_devices: int
    device_link_bw: float

    @property
    def name(self) -> str:
        return f"{self.chip.name}_mesh{self.num_devices}"

    @property
    def total_domains(self) -> int:
        return self.num_devices * self.chip.num_domains

    @property
    def aggregate_hbm_bw(self) -> float:
        return self.num_devices * self.chip.hbm_bw

    @property
    def aggregate_peak_flops(self) -> float:
        return self.num_devices * self.chip.peak_flops


def mesh_topology(
    num_devices: int,
    chip: Topology = TPU_V5E,
    device_link_bw: float | None = None,
) -> MeshTopology:
    """Build the two-tier descriptor for ``num_devices`` chips.

    ``device_link_bw=None`` defaults to ``chip.link_bw`` (the ICI figure
    on the TPU presets) — always a slower rung than ``chip.hbm_bw``."""
    if num_devices < 1:
        raise ValueError(f"num_devices must be >= 1, got {num_devices}")
    return MeshTopology(
        chip=chip,
        num_devices=num_devices,
        device_link_bw=(
            chip.link_bw if device_link_bw is None else float(device_link_bw)
        ),
    )


def pod_as_numa(num_chips: int, chip: Topology = TPU_V5E) -> Topology:
    """Treat a TPU pod as a NUMA machine: one domain per chip, HBM as 'cache'.

    Used by ``core.placement`` to reason about ACC-aligned head sharding: a KV
    tensor resident in chip *i*'s HBM is 'remote' to every other chip, exactly
    as an XCD's L2 is invisible to other XCDs.
    """
    return Topology(
        name=f"{chip.name}_pod{num_chips}",
        num_domains=num_chips,
        slots_per_domain=1,
        cache_bytes=16 * 1024**3,  # HBM per v5e chip
        peak_flops=chip.peak_flops * num_chips,
        hbm_bw=chip.hbm_bw * num_chips,
        link_bw=chip.link_bw,
    )
