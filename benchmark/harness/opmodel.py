"""Operations and bytes one call of a verify kernel needs, from its shapes.

Every verify kernel here evaluates [s]B + [h](-A) with a 4-bit, 64-window
comb over GF(2^255 - 19) in 20 limbs of 13 bits, held in int32. The unit of
work is the 32-bit multiply-add of the schoolbook limb product: this is
int32 VPU work (no MXU), so the compute peak it is held against is a
measured one (benchmark/tools/vpu_peak.py, benchmark/harness/peaks.json).

A kernel's description (benchmark/programs/<xla module>.json) states its
structure -- lanes per call, doublings, additions and their kind, inversions
-- and this file turns that into counts. Squarings count 210 products (the
least a squaring needs), whether or not the kernel has a dedicated squaring.
No PR that claims a gain may change this file.
"""

from __future__ import annotations

NLIMB = 20
FIELD_MUL = NLIMB * NLIMB                   # 400 products
FIELD_SQ = NLIMB * (NLIMB + 1) // 2         # 210 products

# (field multiplications, field squarings) per point operation, as written
# in ops/ed25519_pallas.py and ops/edwards25519.py
POINT_OPS = {
    "double": (4, 4),          # dbl-2008-hwcd: 4 squarings, 4 multiplications
    "niels_add": (7, 0),       # mixed addition with a (y+x, y-x, 2dxy) point
    "extended_add": (9, 0),    # complete addition of two extended points
    "inversion": (11, 254),    # a^(p-2), the curve25519 addition chain
    "field_mul": (1, 0),
}

# bytes per lane: the comb table of -A in the layout the kernel reads
# (16 entries x 3 or 4 field elements x 20 limbs x 4 bytes) is the bulk
TABLE_BYTES = {"niels": 16 * 3 * NLIMB * 4, "extended": 16 * 4 * NLIMB * 4}


def lane_counts(program: dict) -> dict:
    """Field multiplications and squarings one lane (signature slot) costs."""
    mul = sq = 0
    for op, times in program["per_lane"].items():
        m, s = POINT_OPS[op]
        mul += m * times
        sq += s * times
    return {"field_mul": mul, "field_sq": sq,
            "mul_adds": mul * FIELD_MUL + sq * FIELD_SQ}


def call_work(program: dict) -> dict:
    """One call at its compiled shape: padded lanes do the work too."""
    lanes = program["lanes_per_call"]
    per_lane = lane_counts(program)
    per_lane_bytes = (TABLE_BYTES[program["table"]]
                      + program["input_bytes_per_lane"]
                      + program["output_bytes_per_lane"])
    return {"lanes": lanes, "mul_adds": lanes * per_lane["mul_adds"],
            "bytes": lanes * per_lane_bytes, **{
                k: lanes * v for k, v in per_lane.items() if k != "mul_adds"}}
