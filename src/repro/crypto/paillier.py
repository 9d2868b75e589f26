"""Paillier cryptosystem on limb vectors.

* Key generation is host-side python (Miller–Rabin primes) — a one-time,
  per-deployment cost, exactly as in production VFL stacks.
* Enc / Dec / homomorphic ops are vectorized JAX over ciphertext batches;
  ciphertexts live in the *Montgomery domain mod n^2* end to end, so
  homomorphic addition is a single `mont_mul` and scalar multiplication is
  a constant-time Montgomery ladder.
* Plaintext convention (see DESIGN.md §7): protocol plaintexts are
  non-negative integers < n; ring-2^64 share semantics are recovered by
  reducing decrypted integers mod 2^64, so multipliers may be lifted to
  their non-negative residues mod 2^64 and no ciphertext inversion is
  ever required.
* Every hot loop (noise modexp, ladder, ⊕-reduce) dispatches through a
  `crypto.engine.CryptoEngine` — pass `engine=` or rely on the process
  default (`crypto.engine.get_engine()`), which selects the fused Pallas
  kernels on TPU and the jnp library on CPU.  All backends are bit-exact.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.crypto import bigint
from repro.crypto import engine as engine_mod
from repro.crypto.bigint import (LIMB_BITS, Modulus, _add_limbs, add_small,
                                 big_mul_full, from_mont, int_to_bits,
                                 int_to_limbs, limbs_to_int, mod_add,
                                 mod_sub, mont_exp_bits, mont_exp_const,
                                 mont_mul, mul_low, nlimbs, sub_small, to_mont)

_U32 = jnp.uint32


def _eng(engine: "engine_mod.CryptoEngine | None") -> "engine_mod.CryptoEngine":
    return engine if engine is not None else engine_mod.get_engine()


# ---------------------------------------------------------------------------
# Host-side prime generation
# ---------------------------------------------------------------------------

_SMALL_PRIMES = [2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37, 41, 43, 47, 53,
                 59, 61, 67, 71, 73, 79, 83, 89, 97]


def _is_probable_prime(n: int, rng: np.random.Generator, rounds: int = 40) -> bool:
    if n < 2:
        return False
    for p in _SMALL_PRIMES:
        if n % p == 0:
            return n == p
    d, r = n - 1, 0
    while d % 2 == 0:
        d //= 2
        r += 1
    for _ in range(rounds):
        a = 2 + int(rng.integers(0, 1 << 62)) % (n - 3)
        x = pow(a, d, n)
        if x in (1, n - 1):
            continue
        for _ in range(r - 1):
            x = (x * x) % n
            if x == n - 1:
                break
        else:
            return False
    return True


def gen_prime(bits: int, rng: np.random.Generator) -> int:
    while True:
        raw = int.from_bytes(rng.bytes((bits + 7) // 8), "little")
        cand = (raw | (1 << (bits - 1)) | 1) & ((1 << bits) - 1)
        if _is_probable_prime(cand, rng):
            return cand


# ---------------------------------------------------------------------------
# Keys
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PublicKey:
    n: int
    key_bits: int
    mod_n: Modulus
    mod_n2: Modulus
    n_limbs: np.ndarray          # n as Ln-limb vector (for 1 + m*n)

    @property
    def Ln(self) -> int:
        return self.mod_n.L

    @property
    def Ln2(self) -> int:
        return self.mod_n2.L

    @property
    def msg_bits(self) -> int:
        """Safe plaintext magnitude for exact-integer protocol arithmetic."""
        return self.n.bit_length() - 2


@dataclasses.dataclass(frozen=True)
class CRTComponent:
    """Per-prime data for CRT-accelerated decryption (mod p² / q²)."""
    prime: int
    mod_p2: Modulus
    lam_bits: np.ndarray         # bits of p-1
    h_mont: np.ndarray           # L_p(g^{p-1} mod p²)^{-1} · R_p mod p
    hensel_p: np.ndarray         # p^{-1} mod 2^(12·Lp2)
    mod_p: Modulus


@dataclasses.dataclass(frozen=True)
class PrivateKey:
    pub: PublicKey
    lam: int
    lam_bits: np.ndarray         # MSB-first bit vector of lambda
    mu_mont: np.ndarray          # mu * R_n mod n  (fold mu into one mont_mul)
    hensel_n: np.ndarray         # n^{-1} mod 2^(12*Ln2) for exact (u-1)/n
    # CRT acceleration (≈4×: two half-size modexps with half-size exponents)
    crt_p: CRTComponent | None = None
    crt_q: CRTComponent | None = None
    q_pinv_mont: np.ndarray | None = None   # p^{-1}·R_q mod q (CRT combine)
    # persistent fixed-base noise table (crypto.fixed_base), attached by
    # keygen(table_path=…); consumers fall back to the r^n ladder when None
    noise_table: Any = None


@dataclasses.dataclass(frozen=True)
class PeerKey:
    """A *peer's* keypair as this party sees it: public half only.

    Shaped so HE-backend key dicts can mix `PrivateKey` (own) and
    `PeerKey` (everyone else) — every public-key operation reads `.pub`;
    decryption requires the full `PrivateKey` and fails loudly on a
    `PeerKey` (a party can never decrypt under a key it doesn't own).
    """
    pub: PublicKey


def public_key_from_n(n: int, key_bits: int) -> PublicKey:
    """Rebuild a `PublicKey` from the modulus a peer announced (the
    distributed handshake ships only `n`; all derived constants are
    recomputed locally)."""
    mod_n = Modulus.make(n)
    return PublicKey(n=n, key_bits=key_bits, mod_n=mod_n,
                     mod_n2=Modulus.make(n * n),
                     n_limbs=int_to_limbs(n, mod_n.L))


def _crt_component(prime: int, n: int) -> CRTComponent:
    p2 = prime * prime
    mod_p2 = Modulus.make(p2)
    mod_p = Modulus.make(prime)
    # h_p = L_p(g^{p-1} mod p²)^{-1} mod p, g = n+1
    u = pow(n + 1, prime - 1, p2)
    lp = (u - 1) // prime
    h = pow(lp, -1, prime)
    R_p = 1 << (LIMB_BITS * mod_p.L)
    return CRTComponent(
        prime=prime, mod_p2=mod_p2,
        lam_bits=int_to_bits(prime - 1, (prime - 1).bit_length()),
        h_mont=int_to_limbs((h * R_p) % prime, mod_p.L),
        hensel_p=int_to_limbs(pow(prime, -1, 1 << (LIMB_BITS * mod_p2.L)),
                              mod_p2.L),
        mod_p=mod_p)


@obs.key_setup("keygen")
def keygen(key_bits: int, seed: int | None = None, *,
           table_path: str | None = None,
           table_window: int | None = None) -> PrivateKey:
    """Generate a Paillier keypair.  `key_bits` is the modulus size
    (paper: 1024; tests default smaller for CPU speed).

    `table_path` additionally builds (or loads, when the file already
    holds THIS keypair's table — fingerprint-checked) the persistent
    fixed-base noise table and attaches it as `PrivateKey.noise_table`;
    `protocols.PaillierBackend` then routes encryption noise through it
    automatically."""
    rng = np.random.default_rng(seed)
    half = key_bits // 2
    while True:
        p = gen_prime(half, rng)
        q = gen_prime(key_bits - half, rng)
        if p != q and (p * q).bit_length() == key_bits:
            break
    if p > q:
        p, q = q, p          # CRT combine below assumes p < q
    n = p * q
    lam = math.lcm(p - 1, q - 1)
    mod_n = Modulus.make(n)
    mod_n2 = Modulus.make(n * n)
    mu = pow(lam, -1, n)
    R_n = 1 << (LIMB_BITS * mod_n.L)
    R_q = 1 << (LIMB_BITS * Modulus.make(q).L)
    pub = PublicKey(
        n=n, key_bits=key_bits, mod_n=mod_n, mod_n2=mod_n2,
        n_limbs=int_to_limbs(n, mod_n.L))
    noise_table = None
    if table_path is not None:
        from repro.crypto import fixed_base
        window = (fixed_base.DEFAULT_WINDOW if table_window is None
                  else table_window)
        noise_table, _ = fixed_base.ensure_table(n, mod_n2, table_path,
                                                 window=window, rng=rng)
    return PrivateKey(
        pub=pub,
        lam=lam,
        lam_bits=int_to_bits(lam, lam.bit_length()),
        mu_mont=int_to_limbs((mu * R_n) % n, mod_n.L),
        hensel_n=int_to_limbs(pow(n, -1, 1 << (LIMB_BITS * mod_n2.L)),
                              mod_n2.L),
        crt_p=_crt_component(p, n),
        crt_q=_crt_component(q, n),
        q_pinv_mont=int_to_limbs((pow(p, -1, q) * R_q) % q,
                                 Modulus.make(q).L),
        noise_table=noise_table,
    )


# ---------------------------------------------------------------------------
# Plaintext encode / decode (host helpers)
# ---------------------------------------------------------------------------

def encode_ints(pub: PublicKey, xs) -> np.ndarray:
    """Non-negative python ints -> (batch, Ln) limb plaintexts."""
    xs = [int(x) for x in np.atleast_1d(np.asarray(xs, dtype=object))]
    for x in xs:
        if x < 0 or x >= pub.n:
            raise ValueError("plaintext out of range [0, n)")
    return bigint.ints_to_limbs(xs, pub.Ln)


def decode_ints(limbs) -> list[int]:
    """(batch…, L) limb array -> python ints.  Vectorized: one object-dtype
    dot against the radix powers instead of a per-limb python loop per
    element."""
    arr = np.asarray(limbs)
    weights = np.array([1 << (LIMB_BITS * i) for i in range(arr.shape[-1])],
                       dtype=object)
    vals = np.dot(arr.astype(object), weights)
    if arr.ndim == 1:
        return [int(vals)]
    return vals.tolist()


# ---------------------------------------------------------------------------
# Core ops (vectorized; ciphertexts are Montgomery-domain mod n^2)
# ---------------------------------------------------------------------------

def raw_noise(pub: PublicKey, batch: int,
              rng: np.random.Generator | None = None) -> np.ndarray:
    """Fresh encryption randomness r ∈ [1, n), as (batch, Ln2) limbs."""
    from repro.crypto import prng
    r = prng.host_uniform_limbs(pub.n, batch, pub.Ln, rng=rng, lo=1)
    pad = np.zeros((batch, pub.Ln2 - pub.Ln), np.uint32)
    return np.concatenate([r, pad], axis=-1)


def noise_to_mont(pub: PublicKey, r_limbs, engine=None) -> jnp.ndarray:
    """r -> r^n mod n^2, Montgomery domain.  Precomputable offline
    (encryption-noise precompute — amortizes the expensive modexp; the
    runtime's noise pool runs exactly this on the scheduler's thread
    pool, overlapped with the Protocol-3 legs)."""
    eng = _eng(engine)
    rm = eng.to_mont(obs.to_device(r_limbs, _U32), pub.mod_n2)
    return eng.mont_exp_const(rm, pub.n, pub.mod_n2)


def noise_from_table(pub: PublicKey, table, rho_digits,
                     engine=None) -> jnp.ndarray:
    """Table-backed encryption noise: h^ρ mod n², Montgomery domain —
    the DJN short-exponent form of `noise_to_mont` (h = x^n is fixed at
    keygen, ρ is fresh and short), evaluated from a persistent
    `crypto.fixed_base.FixedBaseTable` in ~levels RNS rounds instead of
    an |n|-bit ladder (BENCH fixed_base rows: ≈24× at 1024-bit keys).
    `rho_digits`: (batch, levels) LSB-first window digits
    (`fixed_base.draw_exponent_digits`)."""
    eng = _eng(engine)
    if table.fingerprint != _table_fingerprint(pub):
        from repro.crypto.fixed_base import TableMismatchError
        raise TableMismatchError(
            "noise table was built for a different public key")
    return eng.fixed_base_exp(table, rho_digits, pub.mod_n2)


def _table_fingerprint(pub: PublicKey) -> str:
    from repro.crypto.fixed_base import key_fingerprint
    return key_fingerprint(pub.n)


def encrypt_with_noise(pub: PublicKey, m_limbs, rn_mont,
                       engine=None) -> jnp.ndarray:
    """Enc(m; r) = (1 + m n) * r^n mod n^2, given precomputed r^n.
    With pooled noise, encryption off the critical path costs ~one
    mont_mul."""
    eng = _eng(engine)
    m = obs.to_device(m_limbs, _U32)
    mn = big_mul_full(m, obs.to_device(pub.n_limbs, _U32), pub.Ln2)
    c0 = add_small(mn, 1)
    return eng.mont_mul(eng.to_mont(c0, pub.mod_n2),
                        obs.to_device(rn_mont, _U32), pub.mod_n2)


def encrypt(pub: PublicKey, m_limbs, rng: np.random.Generator | None = None,
            engine=None) -> jnp.ndarray:
    m = jnp.asarray(m_limbs, _U32)
    batch = int(np.prod(m.shape[:-1])) if m.ndim > 1 else 1
    r = raw_noise(pub, batch, rng).reshape(m.shape[:-1] + (pub.Ln2,))
    return encrypt_with_noise(pub, m, noise_to_mont(pub, r, engine),
                              engine)


def decrypt(priv: PrivateKey, c_mont, engine=None) -> jnp.ndarray:
    """-> plaintext limbs (…, Ln)."""
    eng = _eng(engine)
    pub = priv.pub
    u_m = eng.mont_exp_bits(jnp.asarray(c_mont, _U32),
                            jnp.asarray(priv.lam_bits), pub.mod_n2)
    u = eng.from_mont(u_m, pub.mod_n2)
    um1 = sub_small(u, 1)
    k = mul_low(um1, jnp.asarray(priv.hensel_n, _U32), pub.Ln2)[..., :pub.Ln]
    return eng.mont_mul(k, jnp.asarray(priv.mu_mont, _U32), pub.mod_n)


def _dec_component(comp: CRTComponent, c_modp2_mont, eng) -> jnp.ndarray:
    """m_p = L_p(c^{p-1} mod p²) · h_p mod p."""
    u_m = eng.mont_exp_bits(c_modp2_mont, obs.to_device(comp.lam_bits),
                            comp.mod_p2)
    u = eng.from_mont(u_m, comp.mod_p2)
    um1 = sub_small(u, 1)
    k = mul_low(um1, obs.to_device(comp.hensel_p, _U32),
                comp.mod_p2.L)[..., :comp.mod_p.L]
    return eng.mont_mul(k, obs.to_device(comp.h_mont, _U32), comp.mod_p)


class _KeyStatic:
    """Hashable handle on a private key, so it can be a static jit
    argument: keyed on the modulus, as `protocols._HashablePub` is."""

    def __init__(self, priv: PrivateKey):
        self.priv = priv

    def __hash__(self):
        return hash(self.priv.pub.n)

    def __eq__(self, other):
        return (isinstance(other, _KeyStatic)
                and other.priv.pub.n == self.priv.pub.n)


def decrypt_crt(priv: PrivateKey, c_mont, engine=None) -> jnp.ndarray:
    """CRT decryption (≈4× fewer limb-ops than `decrypt`): two half-size
    modexps with half-size exponents, then Garner recombination
      m = m_p + p · ((m_q − m_p) · p^{-1} mod q).
    Returns plaintext limbs (…, Ln), identical to `decrypt` (tested).

    One compiled program per (key, engine, batch shape), the key's
    constants folded in: a call is one launch and counts one
    ``engine_calls["decrypt_crt"]``; the engine entries inside count
    nothing, and only a host-array `c_mont` counts as a transfer."""
    obs.count_engine_call("decrypt_crt")
    return _decrypt_crt_program(_KeyStatic(priv), _eng(engine),
                                obs.to_device(c_mont, _U32))


@functools.partial(jax.jit, static_argnums=(0, 1))
@obs.uncounted()
def _decrypt_crt_program(key: _KeyStatic, eng, c) -> jnp.ndarray:
    priv = key.priv
    pub = priv.pub
    cp, cq = priv.crt_p, priv.crt_q
    # ciphertext is Montgomery mod n²: leave the domain, then reduce
    c_plain = eng.from_mont(c, pub.mod_n2)
    cp2 = eng.to_mont(_reduce_mod(c_plain, cp.mod_p2, eng), cp.mod_p2)
    cq2 = eng.to_mont(_reduce_mod(c_plain, cq.mod_p2, eng), cq.mod_p2)
    m_p = _dec_component(cp, cp2, eng)                  # (…, Lp) < p
    m_q = _dec_component(cq, cq2, eng)                  # (…, Lq) < q
    # Garner: t = (m_q − m_p) mod q;  m = m_p + p·(t·p^{-1} mod q)
    Lq = cq.mod_p.L
    m_p_padq = jnp.pad(m_p, [(0, 0)] * (m_p.ndim - 1)
                       + [(0, max(0, Lq - m_p.shape[-1]))])[..., :Lq]
    t = mod_sub(m_q, _reduce_mod(m_p_padq, cq.mod_p, eng), cq.mod_p)
    u = eng.mont_mul(t, obs.to_device(priv.q_pinv_mont, _U32), cq.mod_p)
    pu = big_mul_full(obs.to_device(int_to_limbs(cp.prime, cp.mod_p.L),
                                    _U32), u, pub.Ln)
    m_p_padn = jnp.pad(m_p, [(0, 0)] * (m_p.ndim - 1)
                       + [(0, pub.Ln - m_p.shape[-1])])
    out, _ = _add_limbs(jnp.broadcast_to(m_p_padn, pu.shape), pu)
    return out


def _fold_below(x: jnp.ndarray, mod: Modulus, eng) -> jnp.ndarray:
    """x mod N for canonical x < R = 2^(12·L): Montgomery round-trip —
    mont_mul's bound holds for a < R, b < N, so to_mont then from_mont is
    an exact general reduction."""
    return eng.from_mont(eng.to_mont(x, mod), mod)


def _reduce_mod(x: jnp.ndarray, mod: Modulus, eng) -> jnp.ndarray:
    """General reduction x mod N for canonical x of any width: split into
    R-sized chunks, Horner fold (acc·R + chunk) with Montgomery ops."""
    L = mod.L
    Lx = x.shape[-1]
    n_chunks = -(-Lx // L)
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, n_chunks * L - Lx)])
    acc = _fold_below(xp[..., (n_chunks - 1) * L:n_chunks * L], mod, eng)
    for i in range(n_chunks - 2, -1, -1):
        acc = eng.to_mont(acc, mod)             # acc · R mod N
        chunk = _fold_below(xp[..., i * L:(i + 1) * L], mod, eng)
        acc = mod_add(acc, chunk, mod)
    return acc


def add_ct(pub: PublicKey, c1, c2, engine=None) -> jnp.ndarray:
    """[[a]] ⊕ [[b]] = [[a + b mod n]]."""
    return _eng(engine).mont_mul(jnp.asarray(c1, _U32),
                                 jnp.asarray(c2, _U32), pub.mod_n2)


def smul_bits(pub: PublicKey, c, exp_bits, engine=None) -> jnp.ndarray:
    """[[a]] ⊗ k = [[a * k mod n]], k given as an MSB-first bit vector
    (traced or constant).  Constant-time ladder."""
    return _eng(engine).mont_exp_bits(jnp.asarray(c, _U32),
                                      jnp.asarray(exp_bits), pub.mod_n2)


def smul_const(pub: PublicKey, c, k: int, engine=None) -> jnp.ndarray:
    if k < 0:
        raise ValueError("lift negative multipliers to residues first")
    # mont_exp_const memoizes the (k, width) bit decomposition
    return _eng(engine).mont_exp_const(jnp.asarray(c, _U32), k, pub.mod_n2)


def hom_sum(pub: PublicKey, c, axis: int = 0, engine=None) -> jnp.ndarray:
    """⊕-reduce a batch of ciphertexts along `axis` (tree reduction —
    the same schedule the mesh collective uses, see distributed/)."""
    eng = _eng(engine)
    c = jnp.asarray(c, _U32)
    c = jnp.moveaxis(c, axis, 0)
    while c.shape[0] > 1:
        half = c.shape[0] // 2
        merged = eng.mont_mul(c[:half], c[half:2 * half], pub.mod_n2)
        if c.shape[0] % 2:
            merged = jnp.concatenate([merged, c[2 * half:]], axis=0)
        c = merged
    return c[0]


def ciphertext_bytes(pub: PublicKey) -> int:
    """Wire size of one ciphertext (serialized canonical form)."""
    from repro.core.comm import ciphertext_wire_bytes
    return ciphertext_wire_bytes(pub.key_bits)
