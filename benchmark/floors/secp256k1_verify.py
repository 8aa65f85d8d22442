"""The least time one chip could take to verify the window's secp256k1
signatures.

Work that is the same whatever implements it: one ECDSA verification is one
double-scalar multiplication u1*G + u2*Q over 256-bit scalars. By the
textbook count (one shared chain, Jacobian coordinates, no windows, no
endomorphism): 256 doublings and 128 additions. A Jacobian doubling on a
curve with a = 0 is 7 field multiplications (2 M + 5 S, a squaring counted
as a multiplication), an addition 16 (11 M + 5 S):

    256 * 7 + 128 * 16 = 3,840 multiplications in GF(p), p of 256 bits

Each 256 x 256-bit product is 32 x 32 products of bytes, two operations
apiece (multiply, add): 2,048 operations, so 3,840 * 2,048 = 7,864,320 a
signature, against the one integer peak `peaks.json` has (`int8_ops_per_s`).
The reduction mod p, the inversion of s mod n and the final comparison are
left out: they are a few per cent of the products and depend on the
representation.

The chip publishes no peak for 32-bit vector integer work, which is what
the program's kernel does (26-bit limbs held in 64-bit integers on a 32-bit
vector unit). So this floor is what the chip's fastest integer unit could do
with the same products, and the share it gives says how far the program is
from THAT, not from what its own unit allows: a very small number, kept so
that a later kernel has a yardstick that its own choice of windows, limbs or
layout cannot move. `units["sig_lanes"]` counts, per block, the signatures
offered to the batched verifier, not the lanes it pads them to.
"""

DOUBLINGS, ADDITIONS = 256, 128
MULS_PER_DOUBLING, MULS_PER_ADDITION = 7, 16
OPS_PER_MUL = 32 * 32 * 2
OPS_PER_SIGNATURE = (DOUBLINGS * MULS_PER_DOUBLING
                     + ADDITIONS * MULS_PER_ADDITION) * OPS_PER_MUL


def floor_seconds(units: dict, peaks: dict) -> tuple[float, str]:
    signatures = sum(units.get("sig_lanes", []))
    return signatures * OPS_PER_SIGNATURE / peaks["int8_ops_per_s"], "ops"
