"""Work of one Algorithm-1 iteration, counted from the cell's shapes.

Products are Montgomery products of the configured algorithm, per kernel
and per width, counted as `benchmarks/kernel_bench.py` counts them:

* r^n noise ladder: 2 products per exponent bit (constant-time ladder),
  exponent n (key_bits bits), at n² (2·key_bits bits);
* fixed-base noise: levels + 1 products per element, levels =
  ⌈rho_bits / window⌉, at n²;
* windowed HE matvec (n rows × m columns, exponent width w, window v):
  n·(2^v − 2) + ⌈w/v⌉·(n·m + (v + 1)·m) products at n²;
* CRT decryption: two ladders per element, at p² and q² (key_bits bits
  each), exponent p − 1 / q − 1 (key_bits/2 bits): 2·2·key_bits/2
  products each.

The Montgomery products of domain changes and single ⊕s (a handful per
element, under 0.2 % of the ladder cell's products) are not counted.

Operations per product depend on the modulus width W alone
(`ops_per_product`): W/8 multiply-accumulates of 8-bit digits, 2 ops
each.  That is a lower bound for any exact implementation: the product
depends on all W bits of each operand, and one 8×8-bit multiply takes in
8 bits from each side, so W/8 of them are the fewest that see every bit.
So the operations counted never exceed what an exact implementation of
the counted products does, and a share of the int8 peak built on them
cannot pass 100 %.  Bytes are the fewest a call moves through HBM: its
inputs and its output once, at their information width.
"""
from __future__ import annotations

import dataclasses

#: kernels by their `pallas_call(name=...)`
KERNELS = ("rns_mont_exp", "rns_he_matvec", "rns_fixed_base", "rns_montmul")


def ops_per_product(width_bits: int) -> float:
    """Int8-op lower bound of one exact modular product at `width_bits`."""
    return 2.0 * (width_bits / 8.0)


def ladder_products(batch: int, exp_bits: int) -> int:
    return 2 * exp_bits * batch


def fixed_base_products(batch: int, levels: int) -> int:
    return (levels + 1) * batch


def matvec_products(n: int, m: int, width: int, window: int) -> int:
    levels = -(-width // window)
    return n * ((1 << window) - 2) + levels * (n * m + (window + 1) * m)


@dataclasses.dataclass
class Work:
    """Products, operations and bytes of one kernel over one iteration."""
    products: int = 0
    ops: float = 0.0
    bytes: float = 0.0

    def add(self, products: int, width_bits: int, nbytes: float) -> None:
        self.products += products
        self.ops += products * ops_per_product(width_bits)
        self.bytes += nbytes


def iteration_work(cfg: dict, feats: dict[str, int], noise: str
                   ) -> dict[str, Work]:
    """Per-kernel work of one iteration of a configuration (the dict of
    its file) whose parties hold `feats` columns each (C first), under a
    traffic's noise form ("ladder" or "tables").  CPs are the first two
    parties, as fixed CP selection makes them."""
    kb = cfg["key_bits"]
    W = 2 * kb                                  # n² width
    nb, width, window = cfg["batch_size"], cfg["exp_width"], cfg["window"]
    names = list(feats)
    cps, noncps = names[:2], names[2:]
    work = {k: Work() for k in KERNELS}

    # (rows encrypted, matvec calls (n, m), rows decrypted) per iteration
    enc_rows = 2 * nb
    matvecs = []
    dec_rows = 0
    for p in cps:                               # under the peer CP's key
        enc_rows += feats[p]
        matvecs.append((nb, feats[p]))
        dec_rows += feats[p]
    for p in noncps:                            # under both CPs' keys
        enc_rows += 2 * feats[p]
        matvecs += [(nb, feats[p])] * 2
        dec_rows += 2 * feats[p]

    if noise == "ladder":                       # the exponent n is shared
        work["rns_mont_exp"].add(ladder_products(enc_rows, kb), W,
                                 enc_rows * 2 * W / 8 + kb / 8)
    elif noise == "tables":
        levels = -(-cfg["rho_bits"] // cfg["table_window"])
        table_bytes = levels * (1 << cfg["table_window"]) * W / 8
        calls = 2 + len(matvecs)                # ⟨d⟩ per CP, masks per leg
        work["rns_fixed_base"].add(
            fixed_base_products(enc_rows, levels), W,
            enc_rows * (cfg["rho_bits"] + W) / 8 + calls * table_bytes)
    else:
        raise ValueError(f"unknown noise form {noise!r}")
    for n, m in matvecs:
        work["rns_he_matvec"].add(matvec_products(n, m, width, window), W,
                                  (n * W + n * m * width + m * W) / 8)
    # CRT: per element two ladders at p², q² (kb bits) with kb/2-bit
    # exponents; the two exponents are shared by every element of a call
    for _prime in ("p", "q"):
        work["rns_mont_exp"].add(ladder_products(dec_rows, kb // 2), kb,
                                 dec_rows * 2 * kb / 8
                                 + len(matvecs) * kb / 16)
    return work


def total_ops(work: dict[str, Work]) -> float:
    return sum(w.ops for w in work.values())


def roofline_seconds(w: Work, peak: dict) -> float:
    """Least time the chip could take for `w`: the larger of its ops
    over the int8 peak and its bytes over the HBM bandwidth."""
    return max(w.ops / peak["int8_ops_per_s"],
               w.bytes / peak["hbm_bytes_per_s"])

