"""Access-pattern pass: pack alignment of the bit-plane codec — pre-run.

* **ACC103 misaligned pack width** (error): a pack/unpack case whose bit
  width does not tile the 32-bit plane word (``32 % bits != 0``) or whose
  block does not fill whole plane words (``block % 32 != 0``); plus the
  static ``DATA_TYPES`` table check that every container width equals
  ``packing.padded_width(nbits)``.

The pass is pure arithmetic over static tables: it needs no jax.
"""
from __future__ import annotations

import dataclasses
from typing import List

from repro.core.packing import DATA_TYPES, padded_width

from .findings import Finding

PASS_NAME = "access-pattern"


@dataclasses.dataclass
class PackCase:
    """One plane pack/unpack: its code width and values per block."""
    name: str
    bits: int
    block: int


#: the (bits, block) pairs the pack and unpack kernels are built for
PACK_CASES = tuple(PackCase(f"kernel/{op}/bits{bits}", bits, 32)
                   for op in ("pack", "unpack") for bits in (4, 8))


# ---------------------------------------------------------------------------
# ACC103: pack-width alignment
# ---------------------------------------------------------------------------

def check_pack_alignment(case: PackCase) -> List[Finding]:
    findings = []
    if 32 % case.bits != 0:
        findings.append(Finding(
            rule="ACC103", severity="error", location=case.name,
            message=(f"pack width {case.bits} does not tile the 32-bit "
                     "plane word — codes straddle word boundaries"),
            pass_name=PASS_NAME))
    if case.block % 32 != 0:
        findings.append(Finding(
            rule="ACC103", severity="error", location=case.name,
            message=(f"block {case.block} does not fill whole 32-bit plane "
                     "words (block % 32 != 0)"),
            pass_name=PASS_NAME))
    return findings


def check_data_types() -> List[Finding]:
    """Static ``DATA_TYPES`` container-width consistency."""
    findings = []
    for name, (nbits, width) in sorted(DATA_TYPES.items()):
        want = padded_width(nbits)
        if width != want:
            findings.append(Finding(
                rule="ACC103", severity="error",
                location=f"core/packing.py:DATA_TYPES[{name}]",
                message=(f"container width {width} != padded_width({nbits})"
                         f" == {want}"),
                pass_name=PASS_NAME))
        if nbits > width:
            findings.append(Finding(
                rule="ACC103", severity="error",
                location=f"core/packing.py:DATA_TYPES[{name}]",
                message=f"nbits {nbits} exceeds container width {width}",
                pass_name=PASS_NAME))
    return findings


# ---------------------------------------------------------------------------
# pass driver
# ---------------------------------------------------------------------------

def run_pass() -> List[Finding]:
    findings: List[Finding] = check_data_types()
    for case in PACK_CASES:
        findings.extend(check_pack_alignment(case))
    return findings
