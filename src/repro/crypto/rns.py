"""RNS/CRT Montgomery pipeline: residue-channel bignum arithmetic.

The CIOS pipeline in `bigint.py` / `kernels/montmul.py` is a *positional*
representation: every Montgomery round threads a carry through the limb
axis, so the inner loop is L sequential rounds of vector MACs.  That
shape interprets fine but leaves the MXU idle — the compiled `pallas`
backend needs a representation whose hot loop is a dense matmul.

This module keeps each big integer as its residues modulo a fixed set of
small *prime channels* (RNS/CRT).  Montgomery reduction over the channel
product follows Bajard et al.'s two-base construction:

* base B (``kB`` channels, product ``B``) carries the Montgomery radix:
  one round computes ``q = -x·y·N⁻¹ mod B`` channel-pointwise;
* base A (``kA`` channels, product ``A``) receives ``q`` through a base
  extension — a (batch, kB) × (kB, kA) matmul — evaluates
  ``t = (x·y + q·N)/B`` pointwise, and sends ``t`` back through a second
  extension.  *All* cross-channel traffic is those two matmuls; every
  other op is embarrassingly channel-parallel.
* one redundant channel ``m_r`` (Shenoy–Kumaresan) makes the second
  extension exact: the first extension may overshoot by ``α·B`` with
  ``α < kB`` (harmless — it only loosens the bound ``t < (kB+2)·N``),
  but the value handed back to base B is reconstructed exactly via
  ``α' = (Σξ'ⱼ·(A/aⱼ) − t) · A⁻¹ mod m_r``.

Exactness of the extension matmuls without 64-bit hardware: channels are
13-bit primes, operands split into 7-bit halves, and each of the four
half-products accumulates to < k·127·127 < 2²⁴ for k ≤ 1040 channels —
integers that size are exactly representable in float32 *regardless of
accumulation order*, so the dots run as plain f32 matmuls (BLAS on CPU,
MXU on TPU) and still return exact integers.

Bit-exact interop with the limb world: `mont_mul` / `mont_exp_bits` /
`he_matvec` here consume and produce the same canonical radix-2¹² limb
vectors as `bigint` (R = 2^(12·L) Montgomery domain).  Internally values
travel in the ·B domain; entry folds the radix change into the
conversion matrix (`to_rns_scaled`, residues of x·B·R⁻¹), exit is one
round against ``R mod N``, and `from_rns` finishes with an exact binary
conditional subtraction — so outputs are the unique canonical
representative, identical to the `bigint` oracle bit for bit
(tests/test_rns.py).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.crypto import bigint
from repro.crypto.bigint import LIMB_BITS, Modulus, nlimbs

_U32 = jnp.uint32
_F32 = jnp.float32

CHANNEL_BITS = 13        # residue channels are primes in (2^11, 2^13)
_SPLIT = 7               # 7-bit halves: 4 f32 dots, each sum < 2^24 exact
_SPLIT_MASK = (1 << _SPLIT) - 1
_MAX_DOT_K = (1 << 24) // (_SPLIT_MASK * _SPLIT_MASK)   # 1040 channels
_ACCUM_CHUNK = 64        # kA-chunk for the no-mod limb accumulation


# ---------------------------------------------------------------------------
# Exact f32 split matmuls
# ---------------------------------------------------------------------------

def _to_f32(x):
    # via int32: the TPU kernel compiler has no direct u32 -> f32 cast;
    # every operand here is < 2^24, so both hops are exact
    return x.astype(jnp.int32).astype(_F32)


def _to_u32(x):
    return x.astype(jnp.int32).astype(_U32)


def _split_halves(x):
    return _to_f32(x & _SPLIT_MASK), _to_f32(x >> _SPLIT)


def _dot(a, b):
    return _to_u32(jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST))


def _mod_u28(x, m):
    """``x mod m`` for uint32 x < 2^28 against the 13-bit channel moduli,
    for the library and XLA paths (kernel bodies always use
    `mod_u28_recip`).  On the CPU a plain `%`: its pipelined integer
    divide beats the f32-reciprocal form (3× end-to-end there).  On the
    TPU the reciprocal form: XLA expands a vector `%` into a long
    sequence that is slow to compile — `to_rns` on 2048 rows at 1024-bit
    keys compiled in 22.6 s with `%`, most of a cold run's compile time
    (described v5e, compiled on a CPU host)."""
    if jax.default_backend() == "tpu":
        return mod_u28_recip(x, m)
    return x % m


def mod_u28_recip(x, m):
    """``x mod m`` for uint32 x < 2^28 and 2^11 < m < 2^13, through an
    f32 reciprocal.  The TPU kernel compiler emulates a vector integer
    remainder with a long instruction sequence: at 1024-bit keys the
    ladder kernel compiled to 4.2× the code with `%` (11.6 MB vs 2.8 MB
    for a v5e).  The quotient estimate floor(f32(x)·f32(1/m)) is within
    one of the true quotient — f32(x) is off by ≤ 8 ≤ m/256, and the two
    roundings add < 2^17·2^-22 — so one select each way makes it exact."""
    xi = x.astype(jnp.int32)
    mi = jnp.asarray(m).astype(jnp.int32)
    q = jnp.floor(xi.astype(_F32) * (1.0 / mi.astype(_F32)))
    r = xi - q.astype(jnp.int32) * mi
    r = jnp.where(r < 0, r + mi, r)
    return jnp.where(r >= mi, r - mi, r).astype(_U32)


def split_matmul_mod(x: jnp.ndarray, t, mods, rem=_mod_u28) -> jnp.ndarray:
    """Exact ``(x @ t) mod mods`` for uint32 entries < 2^13 via four f32
    matmuls of 7-bit halves.  x: (..., k); t: (k, j); mods: (j,)-broadcast.
    Every partial sum is an integer < 2^24 (k ≤ 1040), hence exact in f32
    in any accumulation order; the u32 recombine keeps each congruent
    term below 2^28 before the final reduction `rem`."""
    xl, xh = _split_halves(x)
    tl, th = _split_halves(t)
    ll, lh = _dot(xl, tl), _dot(xl, th)
    hl, hh = _dot(xh, tl), _dot(xh, th)
    mid = rem(lh + hl, mods) << _SPLIT                # lh+hl < 2^25
    top = rem(hh, mods) << (2 * _SPLIT)               # hh < 2^24
    # ll < 2^24, mid < 2^20, top < 2^27 → sum < 2^28
    return rem(ll + mid + top, mods)


def _split_matmul_raw(x: jnp.ndarray, t) -> jnp.ndarray:
    """Exact un-reduced ``x @ t`` as lazy uint32 limb planes; the caller
    must bound k ≤ _ACCUM_CHUNK so the recombined sum stays < 2^31."""
    xl, xh = _split_halves(x)
    tl, th = _split_halves(t)
    ll, lh = _dot(xl, tl), _dot(xl, th)
    hl, hh = _dot(xh, tl), _dot(xh, th)
    return ll + ((lh + hl) << _SPLIT) + (hh << (2 * _SPLIT))


# ---------------------------------------------------------------------------
# Context: per-modulus channel system (host-built, lru-cached)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _prime_pool() -> tuple[int, ...]:
    """13-bit primes, descending (larger channels first → fewer of them)."""
    top = 1 << CHANNEL_BITS
    sieve = np.ones(top, dtype=bool)
    sieve[:2] = False
    for p in range(2, int(top ** 0.5) + 1):
        if sieve[p]:
            sieve[p * p::p] = False
    ps = np.nonzero(sieve)[0]
    ps = ps[ps > (1 << (CHANNEL_BITS - 2))]
    return tuple(int(p) for p in ps[::-1])


@dataclasses.dataclass(frozen=True, eq=False)
class RNSContext:
    """Channel system for one modulus N.  Frozen and identity-hashed so a
    context rides through jit as a static argument; `for_modulus` returns
    the same object per (N, L), so traces cache correctly.

    Channel layout of every state vector: ``[base A | base B | m_r]``
    (kA + kB + 1 = CH channels).  Every per-channel constant sits in its
    channel's lane and the extension matrices are (CH, CH) embeddings, so
    one Montgomery round never slices the lane axis at a base boundary
    (the TPU kernel compiler refuses lane slices that are not aligned to
    its 128-lane tiling).  Numpy members become jit constants.
    """

    value: int                  # N (host int)
    L: int                      # limb count of the radix-2^12 world
    R: int                      # 2^(12 L)
    kA: int
    kB: int
    CH: int
    A: int                      # Π base-A channels  (> (kB+2)²·N)
    B: int                      # Π base-B channels  (> (kB+2)²·N)
    m_r: int                    # redundant channel  (> kA)
    ainv_r: int                 # A⁻¹ mod m_r
    a_mods: np.ndarray          # (kA,)
    b_mods: np.ndarray          # (kB,)
    all_mods: np.ndarray        # (CH,)
    t_b: np.ndarray             # (CH, CH): row bᵢ, column aⱼ or m_r:
                                #   (B/bᵢ) mod that channel; 0 elsewhere
    t_a: np.ndarray             # (CH, CH): row aⱼ, column bᵢ or m_r:
                                #   (A/aⱼ) mod that channel; 0 elsewhere
    vecs: np.ndarray            # (6, CH) per-channel constants in their
                                # own lanes, 0 in the other lanes:
                                #   0: −N⁻¹ mod bᵢ      (B lanes)
                                #   1: (B/bᵢ)⁻¹ mod bᵢ  (B lanes)
                                #   2: (A/aⱼ)⁻¹ mod aⱼ  (A lanes)
                                #   3: N mod [a|r]      (A and r lanes)
                                #   4: B⁻¹ mod [a|r]    (A and r lanes)
                                #   5: A mod bᵢ         (B lanes)
    pow_mat: np.ndarray         # (L, CH): 2^(12 l) mod channel (to_rns)
    pow_scaled: np.ndarray      # (L, CH): (2^(12 l)·B·R⁻¹ mod N) mod ch —
                                # to_rns with the Montgomery-radix change
                                # folded in (value ≡ x·B·R⁻¹ mod N,
                                # magnitude < L·2^12·N, absorbed by the
                                # 2^44 headroom in the base-B floor)
    limb_a: np.ndarray          # (kA, L_out): limbs of A/aⱼ (from_rns)
    a_limbs: np.ndarray         # (L_out,): limbs of A
    L_out: int                  # nlimbs(A) + headroom for Σξ'·(A/aⱼ)
    nj: np.ndarray              # (n_red, L_out): 2^j·N, j descending
    consts: dict                # residue vectors (CH,): 'one' = B mod N,
                                # 'exit' = R mod N


def _residues(v: int, mods: np.ndarray) -> np.ndarray:
    return np.array([v % int(m) for m in mods], np.uint32)


def make_context(value: int, L: int) -> RNSContext:
    """Build the channel system for modulus `value` with limb count L.
    Raises ValueError if the 13-bit prime pool can't cover the modulus
    (≈2048-bit keys / 4096-bit n² is the practical ceiling)."""
    N = int(value)
    if N % 2 == 0 or N < 3:
        raise ValueError("RNS context needs an odd modulus ≥ 3")
    R = 1 << (LIMB_BITS * L)
    pool = [p for p in _prime_pool() if N % p]

    def take(prod_floor):
        picked, prod = [], 1
        while prod <= prod_floor(len(picked)):
            if not pool:
                raise ValueError(
                    f"13-bit RNS prime pool exhausted for a "
                    f"{N.bit_length()}-bit modulus; the channel pipeline "
                    "covers moduli up to ~4096 bits")
            picked.append(pool.pop(0))
            prod *= picked[-1]
        return picked, prod

    # B > max(2·(kB+2)², 2^44)·N keeps the round output t < (kB+2)·N even
    # when both operands carry the scaled-entry magnitude < L·2^12·N
    # (L·2^12 ≤ 2^22 for every supported modulus): x·y ≤ 2^44·N², so
    # t ≤ x·y/B + kB·N < (kB+2)·N.
    b_list, B = take(lambda k: max(2 * (k + 3) ** 2, 1 << 44) * N)
    kB = len(b_list)
    c = kB + 2
    # A > 2·c²·N ≥ c·N bounds from_rns inputs and the second extension
    a_list, A = take(lambda _k: 2 * c * c * N)
    kA = len(a_list)
    if not pool:
        raise ValueError("no prime left for the redundant RNS channel")
    m_r = pool.pop(0)
    assert m_r > kA, "redundant channel must exceed the base-A count"
    if max(kA, kB) + 1 > _MAX_DOT_K or L > _MAX_DOT_K:
        raise ValueError("channel/limb count exceeds the exact-f32 bound")

    a_mods = np.array(a_list, np.uint32)
    b_mods = np.array(b_list, np.uint32)
    all_mods = np.concatenate([a_mods, b_mods, np.array([m_r], np.uint32)])
    ar = a_list + [m_r]
    CH = kA + kB + 1
    a_lanes = list(range(kA)) + [CH - 1]
    b_lanes = slice(kA, kA + kB)

    t_b = np.zeros((CH, CH), np.uint32)
    t_b[b_lanes, a_lanes] = [[(B // bi) % m for m in ar] for bi in b_list]
    t_a = np.zeros((CH, CH), np.uint32)
    t_a[:kA, kA:] = [[(A // aj) % m for m in b_list + [m_r]]
                     for aj in a_list]

    vecs = np.zeros((6, CH), np.uint32)
    vecs[0, b_lanes] = [(-pow(N, -1, bi)) % bi for bi in b_list]
    vecs[1, b_lanes] = [pow(B // bi, -1, bi) for bi in b_list]
    vecs[2, :kA] = [pow(A // aj, -1, aj) for aj in a_list]
    vecs[3, a_lanes] = [N % m for m in ar]
    vecs[4, a_lanes] = [pow(B, -1, m) for m in ar]
    vecs[5, b_lanes] = [A % bi for bi in b_list]

    pow_mat = np.stack([_residues(1 << (LIMB_BITS * l), all_mods)
                        for l in range(L)])
    scale = (B * pow(R, -1, N)) % N
    pow_scaled = np.stack(
        [_residues(((1 << (LIMB_BITS * l)) * scale) % N, all_mods)
         for l in range(L)])

    L_out = nlimbs(A.bit_length() + LIMB_BITS)
    limb_a = np.stack([bigint.int_to_limbs(A // aj, L_out)
                       for aj in a_list])
    a_limbs = bigint.int_to_limbs(A, L_out)
    n_red = max(1, c.bit_length())
    nj = np.stack([bigint.int_to_limbs((1 << j) * N, L_out)
                   for j in range(n_red - 1, -1, -1)])

    consts = {
        "one": _residues(B % N, all_mods),
        "exit": _residues(R % N, all_mods),
    }
    return RNSContext(
        value=N, L=L, R=R, kA=kA, kB=kB, CH=CH, A=A, B=B, m_r=m_r,
        ainv_r=pow(A, -1, m_r), a_mods=a_mods, b_mods=b_mods,
        all_mods=all_mods, t_b=t_b, t_a=t_a, vecs=vecs, pow_mat=pow_mat,
        pow_scaled=pow_scaled, limb_a=limb_a, a_limbs=a_limbs,
        L_out=L_out, nj=nj, consts=consts)


@functools.lru_cache(maxsize=32)
def _context_cached(value: int, L: int) -> RNSContext:
    return make_context(value, L)


def for_modulus(mod: Modulus) -> RNSContext:
    """The (cached) channel system for a `bigint.Modulus`."""
    return _context_cached(mod.value, mod.L)


# ---------------------------------------------------------------------------
# Channel-domain core (shared verbatim by the Pallas kernel bodies)
# ---------------------------------------------------------------------------

def montmul_channels(x, y, mods, t_b, t_a, vecs, *, kA: int, kB: int,
                     ainv_r: int, rem=_mod_u28):
    """One RNS Montgomery round on channel states: returns the residues
    of ``t = x·y·B⁻¹`` with t < (kB+2)·N, given x, y < (kB+2)·N.

    Pure jnp on plain arrays, so kernel bodies trace it inline exactly as
    the library path runs it (`kernels/montmul.py` reuses this function —
    the kernels and the library are the same arithmetic by construction;
    kernels pass ``rem=mod_u28_recip``, an exact remainder too).
    Every step runs on all CH lanes; the zeros of the lane-embedded
    constants keep each base's values in its own lanes, and the redundant
    channel is read out by a masked lane sum, never by a lane slice.
    """
    CH = kA + kB + 1
    nd = max(jnp.ndim(x), jnp.ndim(y))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1,) * (nd - 1) + (CH,),
                                    nd - 1)
    in_b = (lane >= kA) & (lane < kA + kB)

    def lane_r(v):                                       # (..., 1)
        # int32 sum: the kernel compiler has no unsigned reductions
        hit = jnp.where(lane == CH - 1, v.astype(jnp.int32), 0)
        return _to_u32(jnp.sum(hit, axis=-1, keepdims=True))

    s = rem(x * y, mods)                                 # x·y < 2^26
    # q = −x·y·N⁻¹ mod B, channel-pointwise; ξ its mixed-radix form
    # (0 off the B lanes: vecs[0] and vecs[1] vanish there)
    qb = rem(s * vecs[0], mods)                          # < 2^26
    xi = rem(qb * vecs[1], mods)                         # < 2^26
    # first base extension onto the A and r lanes (approximate: may add
    # α·B, α < kB — absorbed by the t < (kB+2)·N bound, never by
    # correctness)
    qhat = split_matmul_mod(xi, t_b, mods, rem)

    # t = (s + q̂·N)/B on base A and the redundant channel (0 on B lanes)
    t_ar = rem(                                          # inner < 2^27
        rem(s + qhat * vecs[3], mods) * vecs[4], mods)   # < 2^26

    # exact second extension A → B (Shenoy–Kumaresan via m_r)
    xi2 = rem(t_ar * vecs[2], mods)                      # A lanes, < 2^26
    ext = split_matmul_mod(xi2, t_a, mods, rem)          # B and r lanes
    rm = lane_r(mods)
    alpha = rem((lane_r(ext) + rm - lane_r(t_ar)) * _U32(ainv_r),
                rm)                                      # < 2^27
    tb = rem(ext + mods - rem(alpha * vecs[5], mods), mods)
    return jnp.where(in_b, tb, t_ar)


# ---------------------------------------------------------------------------
# Conversions limbs <-> channels (exact)
# ---------------------------------------------------------------------------

def _jc(ctx: RNSContext, name: str) -> jnp.ndarray:
    return jnp.asarray(getattr(ctx, name), _U32)


def const_rns(ctx: RNSContext, name: str) -> jnp.ndarray:
    """A named context constant ('one'|'exit') as (CH,)."""
    return jnp.asarray(ctx.consts[name], _U32)


def rns_montmul(ctx: RNSContext, x, y) -> jnp.ndarray:
    return montmul_channels(x, y, _jc(ctx, "all_mods"), _jc(ctx, "t_b"),
                            _jc(ctx, "t_a"), _jc(ctx, "vecs"),
                            kA=ctx.kA, kB=ctx.kB, ainv_r=ctx.ainv_r)


def to_rns(ctx: RNSContext, x: jnp.ndarray) -> jnp.ndarray:
    """(..., L) canonical limbs → (..., CH) channel residues (exact: one
    split-f32 matmul against the 2^(12l) residue matrix)."""
    return split_matmul_mod(x.astype(_U32), _jc(ctx, "pow_mat"),
                            _jc(ctx, "all_mods"))


def to_rns_scaled(ctx: RNSContext, x: jnp.ndarray) -> jnp.ndarray:
    """(..., L) limbs of x ↦ residues of a value ≡ x·B·R⁻¹ (mod N) with
    magnitude < L·2^12·N: the radix change R → B folded into the
    conversion matrix, so entering the ·B domain costs no extra
    Montgomery round (the base-B floor's 2^44 headroom absorbs the
    magnitude — see `make_context`)."""
    return split_matmul_mod(x.astype(_U32), _jc(ctx, "pow_scaled"),
                            _jc(ctx, "all_mods"))


def from_rns(ctx: RNSContext, t: jnp.ndarray) -> jnp.ndarray:
    """(..., CH) channel state with value < (kB+2)·N → (..., L) canonical
    limbs of value mod N.  Exact: mixed-radix reconstruction over base A
    (the redundant channel pins the α'·A overshoot), then a binary
    conditional-subtraction chain brings the value below N."""
    kA = ctx.kA
    ta = t[..., :kA]
    tr = t[..., ctx.CH - 1:]
    am = _jc(ctx, "a_mods")
    xi = _mod_u28(ta * _jc(ctx, "vecs")[2, :kA], am)     # ξ'ⱼ < aⱼ, < 2^26

    t_a = _jc(ctx, "t_a")
    mr = _U32(ctx.m_r)
    sig_r = split_matmul_mod(xi, t_a[:kA, ctx.CH - 1:], mr)   # mod m_r
    alpha = _mod_u28((sig_r + mr - tr) * _U32(ctx.ainv_r), mr)   # < 2^27

    # P = Σⱼ ξ'ⱼ · limbs(A/aⱼ): exact, chunked so lazy limbs stay < 2^31
    limb_a = _jc(ctx, "limb_a")
    acc = jnp.zeros(xi.shape[:-1] + (ctx.L_out,), _U32)
    for c0 in range(0, kA, _ACCUM_CHUNK):
        part = _split_matmul_raw(xi[..., c0:c0 + _ACCUM_CHUNK],
                                 limb_a[c0:c0 + _ACCUM_CHUNK])
        acc = bigint._one_shot_carry(acc + part)
    p = bigint.carry_sweep(acc)
    q = bigint.carry_sweep(alpha * _jc(ctx, "a_limbs"))  # α'·A (α' < 2^13)
    v, _ = bigint._sub_with_borrow(p, q)                 # = t, exact (≥ 0)

    # v < (kB+2)·N → subtract 2^j·N conditionally, MSB-down: v' < N
    nj = _jc(ctx, "nj")
    for j in range(nj.shape[0]):
        d, borrow = bigint._sub_with_borrow(
            v, jnp.broadcast_to(nj[j], v.shape))
        v = jnp.where((borrow == 1)[..., None], v, d)
    return v[..., :ctx.L]


# ---------------------------------------------------------------------------
# Limb-domain ops (drop-in peers of bigint.mont_mul / mont_exp_bits /
# protocols._he_matvec_windowed — bit-exact, jitted per context)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0,))
def mont_mul(ctx: RNSContext, a, b) -> jnp.ndarray:
    """a·b·R⁻¹ mod N on canonical limb vectors (bigint.mont_mul peer)."""
    a, b = jnp.broadcast_arrays(a.astype(_U32), b.astype(_U32))
    # b enters pre-scaled by B·R⁻¹, so one round gives a·b·R⁻¹ directly
    t = rns_montmul(ctx, to_rns(ctx, a), to_rns_scaled(ctx, b))
    return from_rns(ctx, t)


@functools.partial(jax.jit, static_argnums=(0,))
def mont_exp_bits(ctx: RNSContext, base, bits) -> jnp.ndarray:
    """Constant-time ladder base^e on Montgomery-domain limb vectors
    (bigint.mont_exp_bits peer).  bits: (..., nbits) MSB-first."""
    base = jnp.asarray(base, _U32)
    bshape = jnp.broadcast_shapes(base.shape[:-1], bits.shape[:-1])
    base = jnp.broadcast_to(base, bshape + base.shape[-1:])
    bits = jnp.broadcast_to(bits.astype(_U32), bshape + bits.shape[-1:])
    # enter: b̃ = v·R ↦ v·B; the ladder then lives in the ·B domain
    u = to_rns_scaled(ctx, base)
    acc0 = jnp.broadcast_to(const_rns(ctx, "one"), u.shape)

    def step(acc, bit):
        acc = rns_montmul(ctx, acc, acc)
        mul = rns_montmul(ctx, acc, u)
        return jnp.where(bit[..., None] == 1, mul, acc), None

    acc, _ = jax.lax.scan(step, acc0, jnp.moveaxis(bits, -1, 0))
    out = rns_montmul(ctx, acc, const_rns(ctx, "exit"))    # v^e·B ↦ v^e·R
    return from_rns(ctx, out)


def _tree_fold(ctx: RNSContext, c: jnp.ndarray) -> jnp.ndarray:
    """⊕-reduce axis 0 of ·B-domain channel states (log depth)."""
    while c.shape[0] > 1:
        half = c.shape[0] // 2
        merged = rns_montmul(ctx, c[:half], c[half:2 * half])
        if c.shape[0] % 2:
            merged = jnp.concatenate([merged, c[2 * half:]], axis=0)
        c = merged
    return c[0]


@functools.partial(jax.jit, static_argnums=(0, 3))
def he_matvec(ctx: RNSContext, cts, digits, window: int) -> jnp.ndarray:
    """Fixed-window HE matvec on limb vectors: cts (n, L) Montgomery
    ciphertexts, digits (n, m, levels) MSB-first window digits.  Returns
    (m, L) canonical Montgomery limbs — `protocols._he_matvec_windowed`
    peer, bit-exact."""
    cts = jnp.asarray(cts, _U32)
    digits = jnp.asarray(digits, _U32)
    m = digits.shape[1]
    u = to_rns_scaled(ctx, cts)
    one = const_rns(ctx, "one")
    table = [jnp.broadcast_to(one, u.shape), u]
    for _ in range(2, 1 << window):
        table.append(rns_montmul(ctx, table[-1], u))
    table = jnp.stack(table, axis=0)                      # (2^w, n, CH)
    acc0 = jnp.broadcast_to(one, (m, ctx.CH))

    def step(acc, digits_lvl):                            # (n, m)
        for _ in range(window):
            acc = rns_montmul(ctx, acc, acc)
        sel = jnp.take_along_axis(
            table[:, :, None, :], digits_lvl[None, :, :, None], axis=0)[0]
        prod = _tree_fold(ctx, sel)
        return rns_montmul(ctx, acc, prod), None

    acc, _ = jax.lax.scan(step, acc0, jnp.moveaxis(digits, -1, 0))
    out = rns_montmul(ctx, acc, const_rns(ctx, "exit"))
    return from_rns(ctx, out)


@functools.partial(jax.jit, static_argnums=(0,))
def fixed_base_exp(ctx: RNSContext, table_rns, digits) -> jnp.ndarray:
    """Fixed-base windowed exponentiation from a prepared channel-domain
    table: table_rns (levels, 2^w, CH) holds ``h^(d·2^(w·lvl))`` in the
    ·B domain; digits (..., levels) are LSB-first base-2^w digits of the
    exponent.  Returns (..., L) canonical Montgomery-domain limbs of
    h^e·R — the `noise_to_mont` contract."""
    digits = jnp.asarray(digits, _U32)
    table_rns = jnp.asarray(table_rns, _U32)
    acc0 = jnp.broadcast_to(const_rns(ctx, "one"),
                            digits.shape[:-1] + (ctx.CH,))

    def step(acc, lvl_in):
        tab_lvl, dig = lvl_in                              # (2^w, CH), (...,)
        sel = jnp.take(tab_lvl, dig, axis=0)               # (..., CH)
        return rns_montmul(ctx, acc, sel), None

    acc, _ = jax.lax.scan(
        step, acc0, (table_rns, jnp.moveaxis(digits, -1, 0)))
    out = rns_montmul(ctx, acc, const_rns(ctx, "exit"))
    return from_rns(ctx, out)
