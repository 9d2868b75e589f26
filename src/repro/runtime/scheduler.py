"""Algorithm 1 as a thin scheduler over party actors.

The scheduler owns only what the algorithm's *conductor* owns: the batch
schedule, the per-iteration CP selection, the jax key ladder for
Protocol-1 share randomness, and the phase ordering.  All cross-party
values move as typed messages through the Transport, which meters every
`wire_bytes()` and counts communication rounds; all party state lives in
the actors.

The two CPs' joint share arithmetic (Protocol 2, the Beaver legs of
Protocols 1 and 4) is evaluated in-process over the CP pair's states —
the same simulation convention as `mpc.beaver` — with the openings the
parties would exchange accounted through the transport's dealer.

Training is an explicit step-state machine: `step(state) -> state`
advances one iteration over a `runtime.session.TrainState` (everything
an iteration consumes — weights, every stream position, meters), and
`run()` is a thin fold over `step`, so runs can be checkpointed and
resumed bit-exactly, even into a fresh scheduler instance
(tests/test_resumable.py; docs/fault_tolerance.md).

With `LocalTransport` this replays the pre-refactor `train_vfl`
simulation bit-for-bit (losses, weights, per-tag meter bytes — see
tests/test_runtime_parity.py); `PipelinedTransport` overlaps the
data-independent Protocol-3 legs, and with its `concurrent_legs`
default the scheduler dispatches each party's Protocol-1 share
computation and every Protocol-3 masked-matvec/decrypt leg as an
independent pool future (join barrier before Protocol 4), keeping the
latency-step count flat in the party count k and per-iteration
wall-clock below k× the k=2 cost (gauged in BENCH_scaling.json via
benchmarks/fig2_scaling.py; on a single shared CPU host the legs
contend for cores, so absolute speedup needs per-party hardware).
"""
from __future__ import annotations

import functools
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import glm as glm_lib
from repro.core import protocols
from repro.mpc import beaver
from repro.runtime import messages as msg
from repro.runtime import seeds
from repro.runtime.party import DataParty, LabelParty, Party
from repro.runtime import session as session_lib
from repro.runtime.session import TrainState, rebuild_meter
from repro.runtime.transport import LocalTransport, Transport


class TransportDealer:
    """Beaver triples whose online openings (2 values × 2 directions per
    elementwise product) are accounted as `beaver_open` messages."""

    def __init__(self, dealer, transport: Transport, a: str, b: str):
        self._dealer = dealer
        self._transport = transport
        self._a, self._b = a, b

    def elementwise(self, shape):
        n = int(np.prod(shape))
        self._transport.account(msg.BeaverOpen(self._a, self._b,
                                               n_elems=2 * n))
        self._transport.account(msg.BeaverOpen(self._b, self._a,
                                               n_elems=2 * n))
        self._transport.exchange_round()
        return self._dealer.elementwise(shape)


def mask_bound_bits(cfg) -> int:
    """Bit bound on the Protocol-3 pre-mask value (paper §4.3).

    The value a feature owner masks is  v = Σ_i exps[i,j]·⟨d⟩_i  over
    the batch: each offset-lifted exponent is < 2^exp_width, each ring
    share < 2^64, and the sum has ⌈log2 batch_size⌉ carry bits plus one
    slack bit.  Masks are then drawn uniformly from
    [0, 2^(bound + STAT_SEC)), giving 2^-STAT_SEC statistical hiding.

    Args:
      cfg: `VFLConfig` (uses `exp_width`, `batch_size`).
    Returns:
      The bound in bits (an upper bound on ⌈log2 v⌉).
    """
    return 64 + cfg.exp_width + int(np.ceil(np.log2(cfg.batch_size))) + 1


def min_key_bits(cfg) -> int:
    """Smallest key that can carry a live run's masked values:
    mask bound + STAT_SEC statistical-hiding bits + 2 slack bits (the
    masked value + mask sum must stay < n so mod-2^64 share recovery is
    exact)."""
    return mask_bound_bits(cfg) + protocols.STAT_SEC + 2


def validate_key_bits(cfg, bound: int) -> None:
    """Check the Paillier plaintext-capacity bound
    key_bits ≥ bound + STAT_SEC + 2 (see `min_key_bits`).  Enforced for
    BOTH backends: a mock run whose key couldn't carry its own masked
    values would report wire bytes a real deployment can't achieve.

    Args:
      cfg: `VFLConfig` (uses `key_bits`).
      bound: the `mask_bound_bits(cfg)` result.
    Raises:
      ValueError: when the key is too small.
    """
    need = bound + protocols.STAT_SEC + 2
    if cfg.key_bits < need:
        raise ValueError(f"key_bits={cfg.key_bits} too small; need >= {need}")


class VFLScheduler:
    """Drives Algorithm 1 over Party actors.

    Args:
      party_data: sequence of `PartyData`-shaped objects (`.name`,
        `.X` (n, m_p) float features); `party_data[0]` must be C, the
        label holder.
      y: (n,) float labels, held only by C's actor.
      cfg: `core.trainer.VFLConfig` (GLM family, fixed-point widths,
        HE backend, CP-selection mode, seeds).
      backend: optional HE backend (`protocols.PaillierBackend` /
        `MockHEBackend`); built from `cfg` when None.
      transport: optional `Transport`; `LocalTransport` (bit-exact seed
        replay) when None.  A transport exposing an `executor` and
        `concurrent_legs` gets the fan-out schedule: Protocol-1 share
        computations and Protocol-3 legs as independent pool futures.

    `run()` returns a `core.trainer.TrainResult` (weights per party,
    public loss trace, byte meter, round count).
    """

    def __init__(self, party_data: Sequence, y: np.ndarray, cfg,
                 backend=None, transport: Transport | None = None):
        from repro.core import trainer as trainer_lib  # config/backends
        assert party_data[0].name == "C"
        self.cfg = cfg
        self.model = glm_lib.GLMS[cfg.glm]
        self.transport = transport if transport is not None \
            else LocalTransport()
        self.names = [p.name for p in party_data]
        rng = seeds.protocol_rng(cfg.seed)              # protocol randomness
        self.rng = self.transport.wrap_rng(rng)
        self.select_rng = self.transport.cp_select_rng(self.rng, cfg.seed)
        self.batch_rng = np.random.default_rng(cfg.seed)  # batch schedule
        self.jkey = jax.random.key(cfg.seed)              # (matches oracle)
        if backend is None:
            # consumes the protocol stream's first k draws as key seeds
            # (replicated by runtime.seeds.key_seeds for the socket path)
            backend = trainer_lib.make_backend(cfg, self.names, self.rng)
        self.backend = backend
        self.dealer = beaver.DealerTripleSource(
            seed=seeds.dealer_seed(cfg.seed))
        self.mask_bound = mask_bound_bits(cfg)
        validate_key_bits(cfg, self.mask_bound)
        self.parties: list[Party] = [
            LabelParty(party_data[0].name, party_data[0].X, y, cfg,
                       backend, self.rng, self.model)]
        self.parties += [DataParty(p.name, p.X, cfg, backend, self.rng)
                         for p in party_data[1:]]
        self.by_name = {p.name: p for p in self.parties}
        self.transport.bind(self.parties)
        self.n_total = self.parties[0].X.shape[0]
        # noise-pool prefetch: hand the backend the transport's executor
        # so the data-independent r^n modexps overlap Protocol 3
        ex = getattr(self.transport, "executor", None)
        if ex is not None and hasattr(self.backend, "attach_noise_executor"):
            self.backend.attach_noise_executor(ex)
        #: the TrainState the live objects currently embody (identity
        #: check lets the fold skip the per-step restore)
        self._live_state: TrainState | None = None

    @property
    def label_party(self) -> LabelParty:
        return self.parties[0]

    def _fanout(self, thunks):
        """Evaluate independent protocol legs: as pool futures when the
        transport supports concurrent legs, inline otherwise.  Results
        come back in thunk order either way, so everything downstream
        (post order, hence delivery order and the CPs' order-sensitive
        ez chaining) is schedule-independent — the single place that
        keeps the concurrent and sequential schedules bit-identical."""
        ex = self.transport.executor
        if ex is not None and getattr(self.transport, "concurrent_legs",
                                      False):
            futs = [ex.submit(t) for t in thunks]
            return [f.result() for f in futs]
        return [t() for t in thunks]

    def _prefetch_noise(self, cps: tuple[str, str], nb: int) -> None:
        """Schedule this iteration's encryption noise (r^n modexps —
        data-independent) on the transport's pool before Protocol 1 runs,
        so the hot Protocol-3 path pays ~one mont_mul per encryption.
        The raw r draws stay on the conductor thread, so the entropy
        stream is consumed deterministically; the values themselves never
        reach a decrypted quantity, so the trained model is unchanged."""
        be = self.backend
        if not hasattr(be, "prefetch_noise"):
            return
        for cp in cps:
            be.prefetch_noise(cp, nb)          # [[⟨d⟩]] under own key
        for p in self.parties:                 # mask encryptions per leg
            m = p.X.shape[1]
            if p.name in cps:
                be.prefetch_noise(cps[1] if p.name == cps[0] else cps[0], m)
            else:
                for cp in cps:
                    be.prefetch_noise(cp, m)

    # -- one iteration ------------------------------------------------------
    def _select_cps(self) -> tuple[str, str]:
        if self.cfg.cp_selection == "random":
            i = self.select_rng.choice(len(self.names), size=2, replace=False)
            return (self.names[i[0]], self.names[i[1]])
        return (self.names[0], self.names[1])

    def _iteration(self, idx) -> None:
        cfg, tp = self.cfg, self.transport
        nb = len(idx)
        with obs.span("vfl.conductor.begin", rows=nb):
            cps = self._select_cps()
            noncps = [p.name for p in self.parties if p.name not in cps]
            self.jkey, *subkeys = jax.random.split(
                self.jkey, len(self.names) * 2 + 3)
            for p in self.parties:
                p.begin_iteration(idx, cps, nb, self.mask_bound)
            cp0, cp1 = self.by_name[cps[0]], self.by_name[cps[1]]
            ex = tp.executor
            concurrent = ex is not None and getattr(tp, "concurrent_legs",
                                                    False)
            if tp.overlaps_p3:
                self._prefetch_noise(cps, nb)

        # -- Protocol 1: share intermediate results -------------------------
        # Each party's share computation (local matvec + encode + split) is
        # independent, so _fanout runs them on the pool when the transport
        # allows; results are POSTED in party order either way, keeping
        # delivery — and hence the CPs' order-sensitive ez chaining —
        # deterministic.
        with obs.span("vfl.conductor.p1"):
            for out in self._fanout(
                    [functools.partial(p.share_z, subkeys[i])
                     for i, p in enumerate(self.parties)]
                    + [functools.partial(self.label_party.share_y,
                                         subkeys[len(self.names)])]):
                tp.post_all(out)
            tp.pump(order=list(cps))
            mdealer = TransportDealer(self.dealer, tp, cps[0], cps[1])
            ez = None
            if self.model.needs_exp:
                for out in self._fanout(
                        [functools.partial(p.share_ez,
                                           subkeys[len(self.names) + 1 + i],
                                           self.model.exp_sign)
                         for i, p in enumerate(self.parties)]):
                    tp.post_all(out)
                tp.pump(order=list(cps))
                # e^{Σz_p} = Π e^{z_p}: chained Beaver products over the
                # pair (roster order — arrival order is racy under
                # pump_async)
                e0 = cp0.cp.ez_ordered(self.names)
                e1 = cp1.cp.ez_ordered(self.names)
                ez = glm_lib.ez_chain_pair(list(zip(e0, e1)), cfg.f, mdealer)

        ctx = glm_lib.ShareCtx(z=(cp0.cp.z_acc, cp1.cp.z_acc),
                               y=(cp0.cp.y_share, cp1.cp.y_share),
                               ez=ez, f=cfg.f, dealer=mdealer)

        # -- Protocol 2: gradient-operator on shares ------------------------
        with obs.span("vfl.conductor.p2"):
            d0, d1 = self.model.gradient_operator(ctx)
            cp0.cp.d_self, cp1.cp.d_self = d0, d1

        # -- Protocol 3: secure gradients -----------------------------------
        # The two CPs' encrypt legs fan out on the pool when possible.
        with obs.span("vfl.conductor.p3"):
            enc0, enc1 = self._fanout([cp0.announce_enc_d,
                                       cp1.announce_enc_d])
            tp.post(enc0)
            tp.post(enc1)
            if concurrent:
                # Concurrent legs: every masked-matvec / decrypt / unmask
                # leg of all k parties becomes an independent pool future
                # (pump_async) — the k−2 non-CP legs overlap instead of
                # queueing.  pump_async's return is the join barrier
                # before Protocol 4; the ring accumulations it races
                # commute exactly, so the trained model is bit-identical
                # to the sequential schedule (tests/test_runtime_parity.py,
                # k=8).
                for cp in (cp0, cp1):
                    tp.post_all(cp.broadcast_enc_d(noncps))
                tp.pump_async(order=[*cps, *noncps])
            elif tp.overlaps_p3:
                # broadcasts are data-independent of the CP exchange:
                # same sweep
                for cp in (cp0, cp1):
                    tp.post_all(cp.broadcast_enc_d(noncps))
                tp.pump(order=[*cps, *noncps])
            else:
                tp.pump(order=list(cps))
                for p in noncps:
                    for cp in (cp0, cp1):
                        tp.post_all(cp.broadcast_enc_d([p]))
                tp.pump(order=[*noncps, *cps])

        # -- Protocol 4: secure loss ----------------------------------------
        with obs.span("vfl.conductor.p4"):
            l0, l1 = self.model.loss_shares(ctx)
            cp0.cp.l_self = l0
            tp.post(msg.LossShare(cps[1], cps[0], l1))
            tp.pump(order=list(cps))

        # -- stop flag ------------------------------------------------------
        with obs.span("vfl.conductor.flags"):
            tp.post_all(self.label_party.emit_flags(self.names[1:]))
            tp.pump()
            if hasattr(self.backend, "discard_pooled_noise"):
                self.backend.discard_pooled_noise()   # bound pool to one iter

    # -- step-state machine -------------------------------------------------
    # `run()` is a thin fold over `step()`: every iteration consumes and
    # produces an explicit `session.TrainState`, so a run can be paused,
    # checkpointed, and resumed (even in a FRESH scheduler instance)
    # with a bit-identical trajectory — losses, weights, per-tag bytes.

    def init_state(self) -> TrainState:
        """State before iteration 0.  Draws the first epoch permutation
        — the same first `batch_rng` draw the pre-refactor loop made."""
        order = self.batch_rng.permutation(self.n_total)
        return self._capture(it=0, order=order, cursor=0, runtime_s=0.0)

    def _capture(self, it: int, order, cursor: int,
                 runtime_s: float) -> TrainState:
        be = self.backend
        with obs.span("vfl.conductor.capture", it=int(it)):
            pool = 0
            if hasattr(be, "_noise"):
                pool = sum(len(q) for q in be._noise.values())
            shared_select = self.select_rng is self.rng
            state = TrainState(
                it=int(it),
                weights={p.name: np.array(p.W, np.float64)
                         for p in self.parties},
                losses=list(self.label_party.losses),
                stop=bool(self.label_party.stop),
                order=np.asarray(order, np.int64),
                cursor=int(cursor),
                batch_rng=seeds.generator_state(self.batch_rng),
                jkey=obs.to_host(jax.random.key_data(self.jkey), "jkey",
                                 "conductor"),
                protocol_rng=self.rng.state(),
                select_rng=None if shared_select else self.select_rng.state(),
                dealer=self.dealer.state(),
                noise_pool_fill=pool,
                # O(1) prefix view of the append-only ledger — rows are
                # materialized only at serialization time (session.send_rows)
                meter_sends=session_lib.LedgerView(self.transport.meter.sends),
                rounds=int(self.transport.rounds),
                runtime_s=float(runtime_s))
            self._live_state = state
            return state

    def restore(self, state: TrainState) -> None:
        """Load a TrainState into the live objects.  Idempotent — `step`
        restores every iteration, so a freshly deserialized state and
        the fold's own successor states take the identical path.  All
        stream restores are in-place, so aliases (the HE backend's rng
        handle, a LockedRNG wrapper) see the restored position too."""
        with obs.span("vfl.conductor.capture", it=int(state.it)):
            for p in self.parties:
                p.W = np.array(state.weights[p.name], np.float64)
                p.stop = bool(state.stop)
            self.label_party.losses = list(state.losses)
            seeds.restore_generator(self.batch_rng, state.batch_rng)
            self.jkey = jax.random.wrap_key_data(
                jnp.asarray(np.asarray(state.jkey, np.uint32)))
            self.rng.set_state(state.protocol_rng)
            if state.select_rng is not None and self.select_rng is not self.rng:
                self.select_rng.set_state(state.select_rng)
            self.dealer.set_state(state.dealer)
            if hasattr(self.backend, "discard_pooled_noise"):
                # the pool is data-independent scratch; a resumed iteration
                # re-prefetches its own batches (state.noise_pool_fill is 0
                # at every boundary capture)
                self.backend.discard_pooled_noise()
            self.transport.meter = rebuild_meter(state.meter_sends)
            self.transport.rounds = int(state.rounds)
            self._live_state = state

    def step(self, state: TrainState) -> TrainState:
        """One Algorithm-1 iteration as a state transition.  When
        `state` is the object the last capture produced (the fold's
        common case), the live objects already embody it and the
        restore is skipped — a deserialized or older state gets the
        full in-place restore."""
        cfg = self.cfg
        with obs.span("vfl.conductor.step", it=int(state.it)):
            if state is not self._live_state:
                self.restore(state)
            t0 = time.perf_counter()
            order, cursor = state.order, int(state.cursor)
            if cursor + cfg.batch_size > self.n_total:
                order = self.batch_rng.permutation(self.n_total)
                cursor = 0
            idx = order[cursor:cursor + cfg.batch_size]
            cursor += cfg.batch_size
            self._iteration(idx)
            return self._capture(
                it=state.it + 1, order=order, cursor=cursor,
                runtime_s=state.runtime_s + (time.perf_counter() - t0))

    # -- training loop ------------------------------------------------------
    def run(self, state: TrainState | None = None):
        """Fold `step` from `state` (or a fresh `init_state`) until
        max_iter/stop; bit-exact vs the pre-refactor monolithic loop."""
        from repro.core.trainer import TrainResult
        cfg = self.cfg
        if state is None:
            state = self.init_state()
        while state.it < cfg.max_iter and not state.stop:
            state = self.step(state)
        if state is not self._live_state:
            self.restore(state)    # live objects reflect the final state
        return TrainResult(
            weights={n: np.array(w) for n, w in state.weights.items()},
            losses=list(state.losses),
            meter=self.transport.meter,
            runtime_s=state.runtime_s,
            n_iter=state.it,
            rounds=self.transport.rounds)
