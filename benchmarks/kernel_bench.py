"""Crypto kernel microbenchmarks (CPU wall-clock; the Pallas path runs in
interpret mode here — on TPU it is the deployment path).

Rows are dicts {name, us, derived, montmuls?, backend} so `run.py` can
emit both the CSV lines and the machine-readable ``BENCH_crypto.json``
perf-trajectory file.  The library-vs-engine pairs (`montmul`,
`mont_exp`, `he_matvec`) compare the RNS kernels with the library.
These are CPU timings of the Pallas interpreter, not chip numbers.

Guard rows: every ``*_engine_auto_*`` row carries ``guard_vs`` naming
its library counterpart plus ``guard_max_ratio`` — `check_guards`
asserts engine-routed interpret mode never regresses below the library
at any committed size (small moduli route to the library, large ones to
the RNS pipeline which WINS there; docs/engine.md §amortization).  The
``fixed_base`` guard additionally encodes the ≥10× table-vs-ladder
acceptance bound.  `run.py --guards` re-checks the committed
BENCH_crypto.json; the smoke run in scripts/ci.sh checks fresh numbers.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.crypto import bigint, paillier, ring, rns
from repro.crypto import engine as engine_mod
from repro.crypto.bigint import Modulus
from repro.kernels import ops

RNG = np.random.default_rng(7)

# engine-routed interpret mode may not exceed library µs by more than
# this factor (CPU wall-clock jitter allowance)
GUARD_TOLERANCE = 1.15


def check_guards(rows: list[dict]) -> list[str]:
    """Validate every guard-carrying row against its library reference.
    Returns a list of human-readable failures (empty == all pass)."""
    by_name = {r["name"]: r for r in rows}
    failures = []
    for r in rows:
        ref_name = r.get("guard_vs")
        if not ref_name:
            continue
        ref = by_name.get(ref_name)
        if ref is None:
            failures.append(f"{r['name']}: guard reference {ref_name!r} "
                            "missing from the row set")
            continue
        limit = float(r.get("guard_max_ratio", GUARD_TOLERANCE))
        ratio = r["us"] / ref["us"]
        if ratio > limit:
            failures.append(
                f"{r['name']}: {r['us']:.0f}us is {ratio:.2f}x the library "
                f"row {ref_name} ({ref['us']:.0f}us); limit {limit:.2f}x")
    return failures


def _time(fn, *args, warmup: int = 1, reps: int = 3) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6    # µs


def _row(name: str, us: float, derived: str = "", *,
         backend: str = "jnp", montmuls: int | None = None) -> dict:
    r = {"name": name, "us": us, "derived": derived, "backend": backend}
    if montmuls is not None:
        r["montmuls"] = montmuls
    return r


def run(smoke: bool = False) -> list[dict]:
    """smoke=True shrinks every size so CI can run this as a drift check
    in seconds; full mode is the perf-trajectory measurement."""
    rows = []
    mod_bits = (256,) if smoke else (256, 1024)
    batch = 64 if smoke else 256
    # --- Montgomery product: library vs RNS pipeline ---------------------
    eng_auto = engine_mod.CryptoEngine(backend="pallas-interpret",
                                       pipeline="auto")
    for bits in mod_bits:
        n = (1 << bits) - 159
        mod = Modulus.make(n)
        vals = RNG.integers(0, 1 << 62, size=batch).astype(object)
        A = jnp.asarray(bigint.ints_to_limbs([int(v) % n for v in vals],
                                             mod.L))
        jit_lib = jax.jit(lambda a, b: bigint.mont_mul(a, b, mod))
        lib_name = f"montmul_lib_{bits}b_x{batch}"
        us_lib = _time(jit_lib, A, A)
        rows.append(_row(lib_name, us_lib,
                         f"{batch/us_lib:.2f}mul_per_us", montmuls=batch))
        ctx = rns.for_modulus(mod)
        us = _time(lambda a, b: rns.mont_mul(ctx, a, b), A, A)
        rows.append(_row(f"montmul_rns_jnp_{bits}b_x{batch}", us,
                         f"{batch/us:.2f}mul_per_us;lib_vs_rns="
                         f"{us_lib/us:.2f}x", montmuls=batch))
        # full-batch tile: one grid program (interpret overhead is per
        # program, so the honest interpret tiling is the biggest tile)
        us = _time(lambda a, b: ops.rns_montmul(a, b, mod, tile_b=batch,
                                                interpret=True), A, A)
        rows.append(_row(f"montmul_rns_interp_{bits}b_x{batch}", us,
                         f"{batch/us:.2f}mul_per_us;lib_vs_rns="
                         f"{us_lib/us:.2f}x",
                         backend="pallas-interpret", montmuls=batch))
        # engine-routed (auto pipeline): the never-slower-than-library row
        # (jitted like the lib row — engine calls sit inside jitted
        # protocol legs in training)
        us = _time(jax.jit(lambda a, b: eng_auto.mont_mul(a, b, mod)), A, A)
        guard = _row(f"montmul_engine_auto_{bits}b_x{batch}", us,
                     f"route={eng_auto._route(mod)};lib_vs_engine="
                     f"{us_lib/us:.2f}x",
                     backend="pallas-interpret", montmuls=batch)
        guard["guard_vs"] = lib_name
        rows.append(guard)

    # --- mont_exp at 256 bits: library vs engine-routed ----------------
    exp_mod = Modulus.make((1 << 256) - 159)
    exp_batch = 128
    exp_bits_n = 8 if smoke else 16
    base_ints = [int.from_bytes(RNG.bytes(30), "little") % exp_mod.value
                 for _ in range(exp_batch)]
    Bm = bigint.to_mont(
        jnp.asarray(bigint.ints_to_limbs(base_ints, exp_mod.L)), exp_mod)
    ebits = jnp.asarray(np.stack(
        [bigint.int_to_bits(int(e), exp_bits_n)
         for e in RNG.integers(0, 1 << exp_bits_n, size=exp_batch)]))
    exp_mm = 2 * exp_bits_n * exp_batch
    us_lib = _time(jax.jit(lambda b, e: bigint.mont_exp_bits(b, e, exp_mod)),
                   Bm, ebits)
    rows.append(_row(f"mont_exp_lib_256b_x{exp_batch}_e{exp_bits_n}", us_lib,
                     "", montmuls=exp_mm))
    guard = _row(f"mont_exp_engine_auto_256b_x{exp_batch}_e{exp_bits_n}",
                 _time(jax.jit(lambda b, e: eng_auto.mont_exp_bits(
                     b, e, exp_mod)), Bm, ebits),
                 f"route={eng_auto._route(exp_mod)}",
                 backend="pallas-interpret", montmuls=exp_mm)
    guard["guard_vs"] = f"mont_exp_lib_256b_x{exp_batch}_e{exp_bits_n}"
    rows.append(guard)

    # --- mont_exp at the paper's 1024-bit ciphertext modulus: the RNS
    # pipeline is where the fused ladder finally beats the library ------
    if not smoke:
        big_mod = Modulus.make((1 << 1024) - 105)
        big_batch, big_eb = 64, 16
        base_ints = [int.from_bytes(RNG.bytes(127), "little")
                     % big_mod.value for _ in range(big_batch)]
        Bb = bigint.to_mont(
            jnp.asarray(bigint.ints_to_limbs(base_ints, big_mod.L)),
            big_mod)
        eb_big = jnp.asarray(np.stack(
            [bigint.int_to_bits(int(e), big_eb)
             for e in RNG.integers(0, 1 << big_eb, size=big_batch)]))
        big_mm = 2 * big_eb * big_batch
        us_lib = _time(jax.jit(lambda b, e: bigint.mont_exp_bits(
            b, e, big_mod)), Bb, eb_big)
        rows.append(_row(f"mont_exp_lib_1024b_x{big_batch}_e{big_eb}",
                         us_lib, "", montmuls=big_mm))
        us_rns = _time(lambda b, e: ops.rns_mont_exp_fused(
            b, e, big_mod, interpret=True), Bb, eb_big)
        rows.append(_row(f"mont_exp_rns_interp_1024b_x{big_batch}_e{big_eb}",
                         us_rns, f"lib_vs_rns={us_lib/us_rns:.2f}x",
                         backend="pallas-interpret", montmuls=big_mm))
        guard = _row(f"mont_exp_engine_auto_1024b_x{big_batch}_e{big_eb}",
                     _time(jax.jit(lambda b, e: eng_auto.mont_exp_bits(
                         b, e, big_mod)), Bb, eb_big),
                     f"route={eng_auto._route(big_mod)}",
                     backend="pallas-interpret", montmuls=big_mm)
        guard["guard_vs"] = f"mont_exp_lib_1024b_x{big_batch}_e{big_eb}"
        rows.append(guard)

    # --- Paillier primitive ops ------------------------------------------
    key = paillier.keygen(128 if smoke else 256, seed=1)
    pub = key.pub
    kb = pub.key_bits
    enc_batch = 16 if smoke else 64
    m = paillier.encode_ints(pub, [123456] * enc_batch)
    rng = np.random.default_rng(2)
    noise = paillier.noise_to_mont(pub, paillier.raw_noise(pub, enc_batch,
                                                           rng))
    us = _time(jax.jit(lambda mm: paillier.encrypt_with_noise(
        pub, mm, noise)), m)
    rows.append(_row(f"paillier_enc_precomp_noise_x{enc_batch}_{kb}b", us))
    c = paillier.encrypt_with_noise(pub, m, noise)
    us = _time(jax.jit(lambda cc: paillier.decrypt(key, cc)), c)
    rows.append(_row(f"paillier_dec_x{enc_batch}_{kb}b", us))
    us_crt = _time(lambda cc: paillier.decrypt_crt(key, cc), c)
    rows.append(_row(f"paillier_dec_crt_x{enc_batch}_{kb}b", us_crt,
                     f"speedup={us/us_crt:.2f}x"))
    us = _time(jax.jit(lambda cc: paillier.add_ct(pub, cc, cc)), c)
    rows.append(_row(f"paillier_hom_add_x{enc_batch}_{kb}b", us,
                     montmuls=enc_batch))

    # --- HE matvec (Protocol 3 hot path): library vs fused engine --------
    from repro.core import protocols
    mv_m = 4 if smoke else 8
    width = 22
    window = protocols.DEFAULT_WINDOW
    exps = jnp.asarray(RNG.integers(0, 1 << width,
                                    size=(enc_batch, mv_m),
                                    dtype=np.uint32))
    levels = -(-width // window)
    mv_mm = (enc_batch * ((1 << window) - 2)
             + levels * (enc_batch * mv_m + (window + 1) * mv_m))
    if not smoke:
        us_b = _time(lambda cc, ee: protocols.he_matvec(
            pub, cc, ee, width, window=1), c, exps)
        rows.append(_row(f"he_matvec_bitserial_{enc_batch}x{mv_m}_w{width}_{kb}b",
                         us_b, f"{enc_batch*mv_m/us_b:.3f}cells_per_us",
                         montmuls=width * (enc_batch * mv_m + 2 * mv_m)))
    # digits precomputed once, as the trainer's EncodedFeatures does —
    # every windowed row then measures one dispatch into its (jitted)
    # ladder instead of a per-call eager digit decomposition
    dig = jnp.asarray(protocols.window_digits(np.asarray(exps), width,
                                              window))
    us_w = _time(lambda cc, dd: protocols.he_matvec(
        pub, cc, exps, width, window=window, digits=dd), c, dig)
    rows.append(_row(f"he_matvec_lib_window{window}_{enc_batch}x{mv_m}"
                     f"_w{width}_{kb}b", us_w,
                     f"{enc_batch*mv_m/us_w:.3f}cells_per_us",
                     montmuls=mv_mm))
    # guard row measured back-to-back with its reference so the ratio
    # compares like cache/allocator state, not bench-run drift
    guard = _row(f"he_matvec_engine_auto_{enc_batch}x{mv_m}_w{width}_{kb}b",
                 _time(lambda cc, dd: protocols.he_matvec(
                     pub, cc, exps, width, window=window, digits=dd,
                     engine=eng_auto), c, dig),
                 f"route={eng_auto._route(pub.mod_n2)}",
                 backend="pallas-interpret", montmuls=mv_mm)
    guard["guard_vs"] = (f"he_matvec_lib_window{window}_{enc_batch}x{mv_m}"
                         f"_w{width}_{kb}b")
    rows.append(guard)
    eng_r = engine_mod.CryptoEngine(backend="pallas-interpret",
                                    pipeline="rns")
    us_r = _time(lambda cc, dd: protocols.he_matvec(
        pub, cc, exps, width, window=window, digits=dd, engine=eng_r),
        c, dig)
    rows.append(_row(f"he_matvec_rns_window{window}_{enc_batch}x{mv_m}"
                     f"_w{width}_{kb}b", us_r,
                     f"lib_vs_rns={us_w/us_r:.2f}x",
                     backend="pallas-interpret", montmuls=mv_mm))

    # --- fixed-base exponentiation: persistent table vs library ladder ---
    # (the tentpole acceptance row: the encryption-noise modexp h^ρ from
    # a persistent table must beat the r^n library ladder by ≥10× at the
    # paper's 1024-bit ciphertext modulus — guard_max_ratio = 0.1)
    from repro.crypto import fixed_base
    fb_key = paillier.keygen(128 if smoke else 512, seed=3)
    fb_pub = fb_key.pub
    fb_bits = fb_pub.mod_n2.value.bit_length()
    fb_batch = 8 if smoke else 64
    t0 = time.perf_counter()
    table = fixed_base.build_noise_table(fb_pub.n, fb_pub.mod_n2,
                                         rng=np.random.default_rng(4))
    build_us = (time.perf_counter() - t0) * 1e6
    fb_rng = np.random.default_rng(5)
    eng_lib = engine_mod.CryptoEngine(backend="jnp", pipeline="cios")
    raw = paillier.raw_noise(fb_pub, fb_batch, fb_rng)
    lib_name = f"noise_ladder_lib_{fb_bits}b_x{fb_batch}"
    us_nl = _time(jax.jit(lambda rr: paillier.noise_to_mont(
        fb_pub, rr, eng_lib)), jnp.asarray(raw), reps=1)
    rows.append(_row(lib_name, us_nl,
                     f"exp_bits={fb_pub.n.bit_length()}",
                     montmuls=2 * fb_pub.n.bit_length() * fb_batch))
    digits = fixed_base.draw_exponent_digits(table, fb_batch, fb_rng)
    eng_fb = engine_mod.CryptoEngine(backend="pallas-interpret")
    us_fb = _time(lambda dd: paillier.noise_from_table(fb_pub, table, dd,
                                                       eng_fb),
                  jnp.asarray(digits))
    guard = _row(f"fixed_base_table_{fb_bits}b_x{fb_batch}", us_fb,
                 f"window={table.window};levels={table.levels};"
                 f"table_kb={table.nbytes()//1024};"
                 f"build_us={build_us:.0f};"
                 f"speedup_vs_ladder={us_nl/us_fb:.1f}x",
                 backend="pallas-interpret",
                 montmuls=(table.levels + 1) * fb_batch)
    guard["guard_vs"] = lib_name
    # the ≥10× acceptance bound holds at the full 1024-bit measurement;
    # smoke shrinks the modulus to 256 bits, BELOW the RNS amortization
    # threshold (docs/engine.md) where the table walk legitimately loses
    # to the cheap short-limb ladder — there the guard is only a drift
    # tripwire (2×), not a win assertion
    guard["guard_max_ratio"] = 2.0 if smoke else 0.1
    rows.append(guard)

    # --- ring64 matmul: jnp reference vs Pallas(interpret) ---------------
    M, K, N = (32, 64, 16) if smoke else (128, 256, 64)
    a = ring.from_numpy_u64(RNG.integers(0, 1 << 64, (M, K), dtype=np.uint64))
    b = ring.from_numpy_u64(RNG.integers(0, 1 << 64, (K, N), dtype=np.uint64))
    us = _time(lambda x, y: ops.ring_matmul(x, y, tm=min(M, 64),
                                            tn=min(N, 64), interpret=True),
               a, b)
    rows.append(_row(f"ring64_matmul_pallas_{M}x{K}x{N}", us,
                     f"{2*M*K*N/us/1e6:.2f}Gmac_per_s",
                     backend="pallas-interpret"))
    return rows
