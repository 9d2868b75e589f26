"""EFMVFL Protocols 2–4 (Protocol 1 lives in mpc.sharing).

The crown jewel is Protocol 3 (secure gradient computing): the non-local
share of g_p = X_p^T d is evaluated under the *other* party's Paillier key
as a plaintext-matrix × ciphertext-vector product, masked, decrypted by
the key owner and unmasked locally — "only one product between plaintext
matrix and ciphertext vector for each party in each iteration" (paper §5.3).

Engineering notes (DESIGN.md §7):

* Exponent offset trick: X's signed fixed-point entries are lifted by
  OFF = 2^{w−1} so every HE exponent is a short non-negative integer
  (w ≈ 22 bits instead of 64): the key owner removes the OFF·Σ⟨d⟩ term
  *locally* after decryption since it knows its own d-share.  This is a
  beyond-paper micro-optimization (≈3× fewer Montgomery ops) that changes
  no message flow.
* Exact mod-2^64 semantics: all Z_n values stay non-negative integers
  < n, so reducing decrypted integers mod 2^64 recovers ring shares
  exactly (Paillier plaintext wrap never triggers).
* `MockHEBackend` carries the identical mod-2^64 values without
  encryption and meters identical wire bytes — used for large-scale
  benchmarks; `tests/test_protocols.py` asserts mock ≡ Paillier bit-for-bit.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import threading
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.crypto import bigint, fixed_point, paillier, prng, ring
from repro.crypto import engine as engine_mod
from repro.crypto.bigint import mont_mul, mont_one
from repro.crypto.ring import R64

_U32 = jnp.uint32

DEFAULT_EXP_BITS = 22   # fixed-point feature width + sign headroom
STAT_SEC = 40           # statistical masking security (bits)


# ---------------------------------------------------------------------------
# HE matvec:  out_j = ⊕_i  (cts_i ⊗ exps[i, j])      (Protocol 3 line 4)
# ---------------------------------------------------------------------------

def _tree_hom_prod(c: jnp.ndarray, mod) -> jnp.ndarray:
    """⊕-reduce axis 0 of Montgomery-domain ciphertexts (log-depth)."""
    while c.shape[0] > 1:
        half = c.shape[0] // 2
        merged = mont_mul(c[:half], c[half:2 * half], mod)
        if c.shape[0] % 2:
            merged = jnp.concatenate([merged, c[2 * half:]], axis=0)
        c = merged
    return c[0]


DEFAULT_WINDOW = 4      # fixed-window exponentiation (§Perf: 3.7× fewer
                        # Montgomery products than bit-serial at w=22)


def window_digits(exps, width: int, window: int):
    """MSB-first fixed-window digit decomposition: (…, levels) values in
    [0, 2^window).  Works on numpy (EncodedFeatures precompute) and jnp
    (traced fallback) arrays alike."""
    levels = -(-width // window)
    mask = (1 << window) - 1
    cols = [(exps >> ((levels - 1 - lvl) * window)) & mask
            for lvl in range(levels)]
    stack = np.stack if isinstance(exps, np.ndarray) else jnp.stack
    return stack(cols, axis=-1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _he_matvec_bitserial(pub_static, cts, exps, width):
    pub = pub_static.pub
    mod = pub.mod_n2
    bits = fixed_point.int_bits_msb(exps, width)          # (n, m, w)
    one = jnp.broadcast_to(mont_one(mod), cts.shape)       # (n, L2)
    m = exps.shape[1]
    acc0 = jnp.broadcast_to(mont_one(mod), (m, mod.L))

    def step(acc, bits_t):                                # bits_t: (n, m)
        acc = mont_mul(acc, acc, mod)
        sel = jnp.where(bits_t[..., None] == 1,
                        cts[:, None, :], one[:, None, :])  # (n, m, L2)
        prod = _tree_hom_prod(sel, mod)                    # (m, L2)
        return mont_mul(acc, prod, mod), None

    acc, _ = jax.lax.scan(step, acc0, jnp.moveaxis(bits, -1, 0))
    return acc


@functools.partial(jax.jit, static_argnums=(0, 3))
def _he_matvec_windowed(pub_static, cts, digits, window):
    """Fixed-window ladder: precompute c_i^j for j<2^window once per row,
    then one gather + tree-⊕ per digit level.  Montgomery-product count:
      n·(2^w − 2)  precompute  +  levels·(n·m tree + w·m squarings)
    vs bit-serial  width·(n·m + 2m) — ≈ window× fewer in the n·m term.
    `digits`: (n, m, levels) MSB-first window digits (precomputed once
    per batch by EncodedFeatures.make)."""
    pub = pub_static.pub
    mod = pub.mod_n2
    n, L2 = cts.shape
    m = digits.shape[1]
    # power table: (2^w, n, L2)
    table = [jnp.broadcast_to(mont_one(mod), cts.shape), cts]
    for _ in range(2, 1 << window):
        table.append(mont_mul(table[-1], cts, mod))
    table = jnp.stack(table, axis=0)

    acc0 = jnp.broadcast_to(mont_one(mod), (m, mod.L))

    def step(acc, digits_lvl):                            # (n, m)
        for _ in range(window):
            acc = mont_mul(acc, acc, mod)
        # gather c_i^{digit}: (n, m, L2)
        sel = jnp.take_along_axis(
            table[:, :, None, :], digits_lvl[None, :, :, None], axis=0)[0]
        prod = _tree_hom_prod(sel, mod)
        return mont_mul(acc, prod, mod), None

    acc, _ = jax.lax.scan(step, acc0, jnp.moveaxis(digits, -1, 0))
    return acc


class _HashablePub:
    """Hashable wrapper so the public key can be a static jit arg."""

    def __init__(self, pub: paillier.PublicKey):
        self.pub = pub

    def __hash__(self):
        return hash(self.pub.n)

    def __eq__(self, other):
        return isinstance(other, _HashablePub) and other.pub.n == self.pub.n


def he_matvec(pub: paillier.PublicKey, cts: jnp.ndarray,
              exps: jnp.ndarray, width: int,
              window: int = DEFAULT_WINDOW, *,
              digits=None, engine=None) -> jnp.ndarray:
    """cts: (n, L2) Montgomery ciphertexts; exps: (n, m) uint32 < 2^width.
    Returns (m, L2) ciphertexts of Σ_i exps[i,j]·m_i (integer, no wrap).
    window=1 → bit-serial ladder; window=4 (default) → fixed-window.
    `digits` may carry the precomputed MSB-first window decomposition
    (EncodedFeatures.digits, derived at DEFAULT_WINDOW); it is used only
    for window=DEFAULT_WINDOW with a matching level count, else
    re-derived.  `engine` (default: the
    process engine) routes the ladder to the fused Pallas kernel or the
    jnp library — bit-identical either way."""
    eng = engine if engine is not None else engine_mod.get_engine()
    # Route through the engine when it has somewhere better to go than
    # the jitted library ladders here: a mesh or the RNS pipeline (either
    # form).  Library-routed moduli stay on the jitted ladders below.
    engine_routed = eng.sharded or eng._route(pub.mod_n2) != "lib"
    if window <= 1:
        if engine_routed:
            bits = fixed_point.int_bits_msb(exps.astype(_U32), width)
            return eng.he_matvec_windowed(cts, bits, pub.mod_n2, 1)
        return _he_matvec_bitserial(_HashablePub(pub), cts,
                                    exps.astype(_U32), width)
    # precomputed digits are the DEFAULT_WINDOW decomposition — a level-
    # count match alone can coincide across windows, so key on the window
    if digits is None or window != DEFAULT_WINDOW \
            or digits.shape[-1] != -(-width // window):
        digits = window_digits(exps.astype(_U32), width, window)
    if engine_routed:
        return eng.he_matvec_windowed(cts, digits, pub.mod_n2, window)
    return _he_matvec_windowed(_HashablePub(pub), cts,
                               obs.to_device(digits, _U32), window)


# ---------------------------------------------------------------------------
# HE backends
# ---------------------------------------------------------------------------

class PaillierBackend:
    """Real Paillier (128…2048-bit keys).  Each party owns a keypair.

    All hot loops dispatch through `engine` (None → the process default
    CryptoEngine, i.e. fused Pallas kernels on TPU, jnp library on CPU).

    Noise pool: the encryption-noise modexps r^n mod n² are data-
    independent, so once `attach_noise_executor` hands the backend a
    thread pool (the runtime scheduler wires the PipelinedTransport's
    pool), `prefetch_noise` draws r synchronously (keeping the entropy
    stream deterministic) and runs the expensive modexp off-thread,
    overlapped with the Protocol-3 legs.  Consumers match pooled batches
    by (party, count); a miss falls back to the synchronous path, so the
    pool is purely a scheduling optimization — masks cancel exactly and
    noise never reaches a decrypted value, hence the trained model is
    bit-identical with or without it (tests/test_engine.py).

    Fixed-base tables: `attach_table` (or a `PrivateKey.noise_table`
    from `keygen(table_path=…)`, picked up automatically) switches a
    party's noise to the DJN short-exponent form h^ρ evaluated from the
    persistent table (`crypto.fixed_base`) — ~24× cheaper per batch at
    1024-bit keys.  Both the prefetch path and the synchronous fallback
    use the table; masks still cancel exactly, so trained models remain
    bit-identical across noise forms."""

    name = "paillier"

    def __init__(self, keys: dict[str, paillier.PrivateKey],
                 rng: np.random.Generator, engine=None):
        self.keys = keys
        self.rng = rng
        self.engine = engine
        self._noise: dict[str, collections.deque] = collections.defaultdict(
            collections.deque)
        self._noise_lock = threading.Lock()
        self._noise_exec = None
        # fixed-base noise tables per party — seed from any keys that
        # were generated with keygen(table_path=…)
        self.tables: dict[str, object] = {}
        for party, key in keys.items():
            table = getattr(key, "noise_table", None)
            if table is not None:
                self.tables[party] = table

    def key_bits(self, party: str) -> int:
        return self.keys[party].pub.key_bits

    # -- noise pool ---------------------------------------------------------
    def attach_noise_executor(self, executor) -> None:
        self._noise_exec = executor

    def attach_table(self, party: str, table) -> None:
        """Route `party`'s encryption noise through a persistent fixed-
        base table (fingerprint-checked against the party's key)."""
        from repro.crypto import fixed_base
        pub = self.keys[party].pub
        if table.fingerprint != fixed_base.key_fingerprint(pub.n):
            raise fixed_base.TableMismatchError(
                f"table fingerprint does not match {party!r}'s public key")
        self.tables[party] = table

    def attach_tables(self, tables: dict) -> None:
        for party, table in tables.items():
            self.attach_table(party, table)

    def _noise_job(self, party: str, count: int):
        """Draw the randomness for `count` noises synchronously (the
        entropy stream stays deterministic) and return the deferred
        compute closure: table-backed h^ρ when a table is attached, the
        r^n ladder otherwise."""
        pub = self.keys[party].pub
        table = self.tables.get(party)
        with obs.span("vfl.crypto.noise_draw", key_owner=party, rows=count):
            if table is not None:
                from repro.crypto import fixed_base
                digits = fixed_base.draw_exponent_digits(table, count,
                                                         self.rng)
                return (paillier.noise_from_table, pub, table, digits,
                        self.engine)
            raw = paillier.raw_noise(pub, count, self.rng)
            return (paillier.noise_to_mont, pub, raw, self.engine)

    def prefetch_noise(self, party: str, count: int) -> None:
        """Schedule `count` fresh encryption noises under `party`'s key."""
        if self._noise_exec is None or count <= 0:
            return
        fn, *args = self._noise_job(party, count)
        fut = self._noise_exec.submit(fn, *args)
        with self._noise_lock:
            self._noise[party].append((count, fut))

    def _pooled_noise(self, party: str, count: int):
        """Pop a prefetched r^n batch of exactly `count` rows, or None."""
        with self._noise_lock:
            q = self._noise[party]
            for i, (c, fut) in enumerate(q):
                if c == count:
                    del q[i]
                    break
            else:
                return None
        return fut.result()

    def discard_pooled_noise(self) -> None:
        """Drop any unconsumed prefetched batches (the scheduler calls
        this at iteration end so a prefetch/consumption count drift can
        never grow the pool unboundedly — it just wastes one batch and
        the next consumer falls back to the synchronous path)."""
        with self._noise_lock:
            self._noise.clear()

    def _encrypt(self, pub, m_limbs, party: str, count: int) -> jnp.ndarray:
        rn = self._pooled_noise(party, count)
        if rn is None:                          # pool miss: compute inline
            fn, *args = self._noise_job(party, count)
            with obs.span("vfl.crypto.noise_exp", key_owner=party,
                          rows=count):
                rn = fn(*args)
        m = obs.to_device(m_limbs, _U32)
        rn = obs.to_device(rn, _U32).reshape(m.shape[:-1] + (pub.Ln2,))
        return paillier.encrypt_with_noise(pub, m, rn, self.engine)

    # -- protocol ops -------------------------------------------------------
    def encrypt_share(self, party: str, d: R64) -> jnp.ndarray:
        with obs.span("vfl.crypto.encrypt", key_owner=party,
                      rows=d.lo.shape[0] if d.lo.ndim else 1):
            pub = self.keys[party].pub
            m = fixed_point.r64_to_limbs(d, pub.Ln)
            count = int(np.prod(m.shape[:-1])) if m.ndim > 1 else 1
            return self._encrypt(pub, m, party, count)

    def matvec(self, party: str, cts, exps, width, digits=None
               ) -> jnp.ndarray:
        rows, cols = exps.shape
        with obs.span("vfl.crypto.matvec", key_owner=party, rows=rows,
                      cols=cols):
            return he_matvec(self.keys[party].pub, cts, exps, width,
                             digits=digits, engine=self.engine)

    def add_mask(self, party: str, cts, mask_ints: list[int]) -> jnp.ndarray:
        """cts ⊕ Enc(R) with fresh noise — masks AND re-randomizes."""
        with obs.span("vfl.crypto.add_mask", key_owner=party,
                      rows=len(mask_ints)):
            pub = self.keys[party].pub
            with obs.span("vfl.crypto.to_limbs", rows=len(mask_ints)):
                m = bigint.ints_to_limbs(mask_ints, pub.Ln)
            cr = self._encrypt(pub, m, party, len(mask_ints))
            return paillier.add_ct(pub, cts, cr, self.engine)

    def decrypt_to_r64(self, party: str, cts) -> R64:
        key = self.keys[party]
        if not hasattr(key, "lam"):     # paillier.PeerKey: public half only
            raise PermissionError(
                f"cannot decrypt under {party!r}: this backend view holds "
                "only the peer's public key (distributed runtime)")
        with obs.span("vfl.crypto.decrypt", key_owner=party,
                      rows=cts.shape[0] if cts.ndim > 1 else 1):
            dec = paillier.decrypt_crt(key, cts, engine=self.engine)
            return fixed_point.limbs_to_r64(dec)


class MockHEBackend:
    """Carries the identical mod-2^64 integers without encryption (for
    large benchmarks).  Message flow, masking and byte accounting are
    identical to PaillierBackend; tests assert value-equality."""

    name = "mock"

    def __init__(self, key_bits: int = 1024):
        self._key_bits = key_bits

    def key_bits(self, party: str) -> int:
        return self._key_bits

    def encrypt_share(self, party: str, d: R64) -> R64:
        return d

    def matvec(self, party: str, cts: R64, exps, width, digits=None) -> R64:
        xs = exps.astype(_U32)
        xa = R64(jnp.zeros_like(xs), xs)                 # lift u32 exponents
        # (n, m) exps × (n,) cts -> (m,)
        prod = ring.mul(xa, R64(cts.hi[:, None], cts.lo[:, None]))
        return ring.sum_axis(prod, 0)

    def add_mask(self, party: str, cts: R64, mask_ints: list[int]) -> R64:
        m = ring.from_numpy_u64(
            np.array([v % (1 << 64) for v in mask_ints], np.uint64))
        return ring.add(cts, m)

    def decrypt_to_r64(self, party: str, cts: R64) -> R64:
        return cts


# ---------------------------------------------------------------------------
# Protocol 3 — secure gradient computing
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EncodedFeatures:
    """A party's local features in protocol form."""
    x_int: np.ndarray        # (n, m_p) int32 signed fixed-point
    exps: np.ndarray         # (n, m_p) uint32 = x_int + OFF
    fx: int
    width: int
    digits: np.ndarray | None = None  # (n, m_p, levels) MSB-first window
                                      # digits at DEFAULT_WINDOW — derived
                                      # once here, sliced per batch, so
                                      # he_matvec never re-decomposes

    @staticmethod
    def make(x: np.ndarray, fx: int, width: int = DEFAULT_EXP_BITS):
        xi = np.rint(np.asarray(x, np.float64) * (1 << fx)).astype(np.int64)
        off = 1 << (width - 1)
        if np.any(np.abs(xi) >= off):
            raise ValueError("feature fixed-point exceeds exponent width; "
                             "raise width or normalize features")
        exps = (xi + off).astype(np.uint32)
        return EncodedFeatures(
            x_int=xi.astype(np.int32),
            exps=exps,
            fx=fx, width=width,
            digits=window_digits(exps, width, DEFAULT_WINDOW)
            .astype(np.uint32))

    def slice(self, idx) -> "EncodedFeatures":
        return EncodedFeatures(
            x_int=self.x_int[idx], exps=self.exps[idx],
            fx=self.fx, width=self.width,
            digits=None if self.digits is None else self.digits[idx])


def mask_ints(bound_bits: int, m: int, rng: np.random.Generator) -> list[int]:
    """Statistical masks R_j uniform in [0, 2^(bound_bits + STAT_SEC))."""
    return prng.host_uniform_below(1 << (bound_bits + STAT_SEC), m, rng=rng)


def offset_correction(d_share: R64, width: int) -> R64:
    """OFF · Σ_i ⟨d⟩_i  mod 2^64 — the key owner's local correction."""
    s = ring.sum_axis(d_share, 0)
    return ring.mul_pub_int(s, 1 << (width - 1))


def mask_to_r64(R: list[int]) -> R64:
    """The mask owner's local mod-2^64 image of R (for unmasking)."""
    return ring.from_numpy_u64(np.array([r % (1 << 64) for r in R],
                                        np.uint64))


# --- per-party steps (pure: no metering — byte accounting happens at the
# transport boundary via runtime.messages.Message.wire_bytes()) ------------

def local_grad_share(feats: EncodedFeatures, d_self: R64) -> R64:
    """Protocol 3 line 2 — a CP's local term X_p^T ⟨d⟩_p."""
    return _local_grad(obs.to_device(feats.x_int.T), d_self)


@ring.share_program("local_grad_share")
def _local_grad(x_t: jnp.ndarray, d: R64) -> R64:
    g = ring.matmul(x_t, R64(d.hi[:, None], d.lo[:, None]))
    return R64(g.hi[:, 0], g.lo[:, 0])


def masked_matvec(backend, key_owner: str, d_ct, feats: EncodedFeatures,
                  mask_bound_bits: int, rng: np.random.Generator):
    """Protocol 3 lines 4–6 at the feature owner: plaintext-matrix ×
    ciphertext-vector under `key_owner`'s key, statistically masked and
    re-randomized.  Returns (enc_masked, R_mod264) — the caller ships
    enc_masked as a `P3.masked_grad` message and keeps R for unmasking."""
    m = feats.exps.shape[1]
    enc_g = backend.matvec(key_owner, d_ct, obs.to_device(feats.exps),
                           feats.width, digits=feats.digits)
    R = mask_ints(mask_bound_bits, m, rng)
    return backend.add_mask(key_owner, enc_g, R), mask_to_r64(R)


def decrypt_offset_corrected(backend, key_owner: str, enc_masked,
                             d_own: R64, width: int) -> R64:
    """Protocol 3 line 7 at the key owner: decrypt, reduce mod 2^64,
    remove the OFF·Σ⟨d⟩ exponent-lift term (local: it knows its d-share).
    The result goes back as a `P3.unmasked_share` message."""
    w = backend.decrypt_to_r64(key_owner, enc_masked)
    return ring.sub(w, offset_correction(d_own, width))


# --- whole-protocol compositions (simulation evaluates both parties'
# local steps in one call; tests and oracles use these) --------------------

def secure_gradient_cp(
    backend, *,
    p0: str, p1: str,
    feats: EncodedFeatures,
    d_self: R64,                  # ⟨d⟩_{p0}, held by p0
    d_other_ct,                   # [[⟨d⟩_{p1}]]_{p1}, received from p1
    d_other_share: R64,           # ⟨d⟩_{p1} (used only for p1's local step)
    mask_bound_bits: int,
    rng: np.random.Generator,
) -> R64:
    """Protocol 3 with P0 = a computing party.  Returns g_{p0} as ring
    fixed-point with (fx + f) fractional bits."""
    g_self = local_grad_share(feats, d_self)
    enc_masked, Rr = masked_matvec(backend, p1, d_other_ct, feats,
                                   mask_bound_bits, rng)
    w = decrypt_offset_corrected(backend, p1, enc_masked, d_other_share,
                                 feats.width)
    return ring.sub(ring.add(g_self, w), Rr)


def secure_gradient_noncp(
    backend, *,
    party: str, cps: tuple[str, str],
    feats: EncodedFeatures,
    d_cts: dict,                  # {cp: [[⟨d⟩_cp]]_cp} received broadcasts
    d_shares: dict,               # {cp: ⟨d⟩_cp} (for each CP's local step)
    mask_bound_bits: int,
    rng: np.random.Generator,
) -> R64:
    """Algorithm 1 lines 17–21: a non-computing party computes its gradient
    under BOTH CPs' keys.  g_p = Σ_cp (dec_cp − R_cp-correction)."""
    m = feats.exps.shape[1]
    total = ring.zeros((m,))
    for cp in cps:
        enc_masked, Rr = masked_matvec(backend, cp, d_cts[cp], feats,
                                       mask_bound_bits, rng)
        w = decrypt_offset_corrected(backend, cp, enc_masked, d_shares[cp],
                                     feats.width)
        total = ring.add(total, ring.sub(w, Rr))
    return total


