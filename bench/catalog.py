"""The benchmark's spot catalog: a seeded offering table of SpotLake's shape.

Both the system under test and the reference solve over this table, so it
is generated here, from ``--seed``, and not by the program.  The arithmetic
is a copy of ``repro.core.market.generate_catalog`` / ``_mk_offering`` as of
the commit that added this benchmark: the same RNG draws in the same order,
so ``offerings(seed, regions, families)`` equals
``generate_catalog(seed, regions, families)`` field for field.  A change to
the program's generator therefore cannot move the benchmark's data.

Shape (SpotLake archive, IISWC 2022): family c/m/r × generation 5–8 ×
vendor i/a/g × specialization ""/n/d/dn × 8 sizes × region × 3 AZs;
Graviton has no specialized variants and gen 5 has no "dn".  Spot prices
are decoupled from performance; T3 (multi-node capacity) shrinks with size.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import numpy as np

AZS_PER_REGION = 3
#: family -> (GiB per vCPU, on-demand $ per vCPU-hour at gen 6)
FAMILY_SPECS = {"m": (4.0, 0.0480), "c": (2.0, 0.0425), "r": (8.0, 0.0630)}
#: specialization suffix -> (on-demand multiplier, kind)
SPECIALIZATIONS = {"": (1.00, "general"), "n": (1.35, "network"),
                   "d": (1.25, "disk"), "dn": (1.55, "network+disk")}
#: vendor -> (per-core CoreMark multiplier, price multiplier)
VENDORS = {"i": (1.00, 1.00), "a": (0.97, 0.90), "g": (0.90, 0.80)}
SIZES = {"large": 2, "xlarge": 4, "2xlarge": 8, "4xlarge": 16,
         "8xlarge": 32, "12xlarge": 48, "16xlarge": 64, "24xlarge": 96}
GEN6_CORE_SCORE = 23_000.0
GEN_SCORE_STEP = 0.09
GEN_PRICE_STEP = 0.045

#: the fields of one offering, in ``repro.core.market.Offering`` order
FIELDS = ("offering_id", "instance_type", "family", "generation", "vendor",
          "specialization", "size", "region", "az", "vcpus", "mem_gib",
          "od_price", "spot_price", "bs_core", "sps_single", "t3",
          "interruption_freq")


def _offering(rng: np.random.Generator, family: str, gen: int, vendor: str,
              spec_suffix: str, size: str, region: str, az: str,
              od_base_per_vcpu: float) -> Dict:
    vcpus = SIZES[size]
    mem_per_vcpu, _ = FAMILY_SPECS[family]
    spec_mult, spec_kind = SPECIALIZATIONS[spec_suffix]
    vendor_score, vendor_price = VENDORS[vendor]
    od = (od_base_per_vcpu * vcpus * spec_mult * vendor_price
          * (1.0 + GEN_PRICE_STEP * (gen - 6)))
    size_frac = math.log2(vcpus / 2.0) / math.log2(48.0)
    discount = float(np.clip(rng.beta(5.0, 2.5) * (0.68 + 0.42 * size_frac),
                             0.25, 0.93))
    spec_slack = 1.0 + 0.40 * (spec_mult - 1.0)
    spot = od * (1.0 - discount) / spec_slack
    bs_core = (GEN6_CORE_SCORE * vendor_score
               * (1.0 + GEN_SCORE_STEP * (gen - 6))
               * float(rng.normal(1.0, 0.015)))
    t3_mean = 42.0 / math.sqrt(vcpus / 2.0) * (1.0 - 0.08 * (gen - 5))
    t3 = int(np.clip(rng.poisson(max(t3_mean, 0.5)), 0, 50))
    sps_single = int(rng.choice([1, 2, 3], p=[0.15, 0.25, 0.60]))
    if t3 >= 25:
        sps_single = 3
    interruption_freq = int(np.clip(4 - t3 // 10 + rng.integers(-1, 2), 0, 4))
    itype = f"{family}{gen}{vendor}{spec_suffix}.{size}"
    return dict(offering_id=f"{itype}@{az}", instance_type=itype,
                family=family, generation=gen, vendor=vendor,
                specialization=spec_kind, size=size, region=region, az=az,
                vcpus=vcpus, mem_gib=mem_per_vcpu * vcpus,
                od_price=round(od, 4), spot_price=round(max(spot, 0.001), 4),
                bs_core=round(bs_core, 1), sps_single=sps_single, t3=t3,
                interruption_freq=interruption_freq)


def offerings(seed: int, regions: Sequence[str], families: Sequence[str],
              generations: Sequence[int] = (5, 6, 7, 8),
              zones: Optional[Sequence[str]] = None) -> List[Dict]:
    """The seeded offering table of one deployment, as dicts of
    :data:`FIELDS`.  ``zones`` keeps only those availability zones (the
    draws of the others are still made, so a zone's prices do not depend
    on the filter)."""
    rng = np.random.default_rng(seed)
    out: List[Dict] = []
    for region in regions:
        for family in families:
            _, od_vcpu = FAMILY_SPECS[family]
            for gen in generations:
                for vendor in VENDORS:
                    specs = [""] if vendor == "g" else (
                        ["", "n", "d"] if gen == 5 else ["", "n", "d", "dn"])
                    for spec_suffix in specs:
                        for size in SIZES:
                            for az_i in range(AZS_PER_REGION):
                                az = f"{region}{chr(ord('a') + az_i)}"
                                out.append(_offering(
                                    rng, family, gen, vendor, spec_suffix,
                                    size, region, az, od_vcpu))
    if zones is not None:
        out = [o for o in out if o["az"] in zones]
    return out


def deployment_offerings(config: Dict, seed: int) -> List[Dict]:
    """The offering table a configuration file describes, from ``seed``."""
    return offerings(seed, config["regions"], config["families"],
                     config["generations"], config.get("zones"))
