"""Process-isolated EFMVFL parties: one TCP server per party.

`PartyServer` hosts exactly one `Party`/`LabelParty` actor in its own OS
process and speaks nothing but codec frames (`runtime.codec`) over TCP:

  bind → handshake → mesh → key exchange → [resume] → iterate → serve
  → shutdown

Durability.  With a checkpoint directory configured, the party persists
its OWN `runtime.session.TrainState` slice (weights, stream cursors,
meter ledgers — never a share, never key material) through
`checkpoint.CheckpointManager` every `cfg.checkpoint_every` iterations,
*before* acking `iter_done`; on a resume handshake it offers its valid
steps, rolls back to the cluster-agreed common step, and reports its
audited stream counters.  See docs/fault_tolerance.md.

Topology.  Every party listens on a loopback/LAN port.  The conductor
(`launch.cluster.SocketCluster`) connects to every party and drives the
run with `Control` frames; the parties form a full mesh among
themselves (party i initiates to every lower-index peer, accepts every
higher-index one) and exchange *all* protocol traffic directly — the
conductor never sees a share or a ciphertext, preserving the paper's
no-third-party trust model.

Determinism.  The handshake carries the run seed; every party re-derives
the streams the single-process scheduler owns so the trained model is
bit-identical to `LocalTransport` (losses, weights, per-tag bytes).
The derivations live in ONE registry, `runtime/seeds.py`:

  * batch schedule      — `default_rng(seed)` (identical replicas)
  * Protocol-1 shares   — `jax.random.key(seed)` ladder (identical)
  * Beaver triples      — `DealerTripleSource(seed+1)` replicas; non-CP
    parties `skip()` the pair's per-iteration draw count to stay aligned
  * Paillier key seeds  — first k draws of `default_rng(seed+90001)`,
    matching `trainer.make_backend`; each party generates only its OWN
    keypair and learns the peers' public `n` through the conductor
    (a real deployment would replace the seed derivation with local
    entropy — the message flow would not change)
  * masks & noise       — per-party stream `default_rng([seed, 90101,
    index])`.  Mask values differ from the single-process run, which is
    invisible in the result: Protocol-3 masks cancel exactly and
    encryption noise never reaches a decrypted value.
  * CP selection        — conductor-owned `default_rng(seed+90002)`
    (the `PipelinedTransport` convention), broadcast per iteration.

Joint CP arithmetic runs as `mpc.pairwise` legs: the Beaver openings
that the simulation only *accounted* are real `beaver_open` frames here
(identical per-tag bytes — 2 ring elements per product element per
direction).

Event loop.  The actor is single-threaded; reader threads only enqueue
decoded frames.  While waiting for anything, the server keeps
dispatching other protocol messages to the actor (selective receive),
so a computing party serves decrypt requests even while blocked in an
opening exchange.  Messages that must not hit the actor early are
stashed: `beaver_open` frames queue per-peer for the leg openers,
Protocol-1 shares queue until `begin_iteration` has run (they can
arrive before the conductor's `iter` frame), and serving-path score
shares queue until C opens an inference batch.  The conductor's
iteration barrier (every party acks `iter_done`, and no party acks
before consuming everything it needed) guarantees the network is quiet
between iterations.
"""
from __future__ import annotations

import os
import socket
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager, party_checkpoint_dir
from repro.core import glm as glm_lib
from repro.core import protocols
from repro.crypto import paillier, ring
from repro.crypto import engine as engine_mod
from repro.crypto.ring import R64
from repro.mpc import beaver, pairwise
from repro.runtime import codec as codec_lib
from repro.runtime import messages as msg
from repro.runtime import seeds as seeds_lib
from repro.runtime import session as session_lib
from repro.runtime.dispatch import DispatchCore, PeerLost
from repro.runtime.party import DataParty, LabelParty
from repro.runtime.policy import RetryPolicy
from repro.runtime.scheduler import mask_bound_bits, validate_key_bits
from repro.runtime.transport import SocketTransport

CONDUCTOR = "conductor"

#: re-export for importers: the event-loop core (and its peer-loss
#: exception) moved to runtime/dispatch.py so serving shares it
PeerLost = PeerLost
#: historical module constant, now derived from the central policy
#: block (runtime/policy.py) — kept for importers
IO_TIMEOUT_S = RetryPolicy.from_env().io_timeout_s

_P1_TYPES = (msg.ZShare, msg.YShare, msg.EzShare)


#: re-export: the per-party key seeds, exactly as `trainer.make_backend`
#: draws them (see runtime/seeds.py — the stream registry).
derive_key_seeds = seeds_lib.key_seeds


class PartyServer:
    """One EFMVFL party as a network server.  See the module docstring
    for the protocol; `run()` is the process entry point."""

    def __init__(self, name: str, X: np.ndarray,
                 y: Optional[np.ndarray] = None, host: str = "127.0.0.1",
                 io_timeout: float | None = None,
                 checkpoint_dir: Optional[str] = None,
                 wire: Optional[dict] = None):
        self.name = name
        self.X = np.asarray(X, np.float64)
        self.y = None if y is None else np.asarray(y, np.float64)
        if name == "C" and self.y is None:
            raise ValueError("party C must hold the label vector")
        self.host = host
        # `wire` is the launcher-shipped link configuration: {"policy":
        # RetryPolicy dict, "chaos": ChaosProfile dict | None,
        # "compression": scheme}.  It rides the SPAWN ARGS, not the
        # handshake — the party needs its deadlines before the first
        # handshake frame can travel.
        self.wire = dict(wire or {})
        self.policy = RetryPolicy.from_dict(self.wire.get("policy"))
        if io_timeout is not None:       # explicit override wins
            self.policy = RetryPolicy.from_dict(
                dict(self.policy.to_dict(), io_timeout_s=float(io_timeout)))
        self.io_timeout = self.policy.io_timeout_s
        # party-LOCAL durable state: each party checkpoints only its own
        # TrainState slice under <dir>/party_<name>; shares and private
        # key material never leave the process (keys are seed-derived and
        # re-derived on resume — see docs/fault_tolerance.md)
        self.checkpoint_dir = None if checkpoint_dir is None else \
            party_checkpoint_dir(checkpoint_dir, name)
        self.ckpt: Optional[CheckpointManager] = None
        self.resume = False
        self.backend = None
        self.actor = None
        self._p1_open = False
        self._scoring = False
        self._flags_seen = 0
        self._unmask_served = 0
        self._dealer_draws = 0
        # selective-receive core + stashes are built in _run once the
        # transport exists (runtime/dispatch.py); the match predicates
        # close over the phase flags above
        self.core: Optional[DispatchCore] = None
        self._pending_p1 = None
        self._pending_wx = None
        self._opens = None

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def run(self, ready_queue=None) -> None:
        """Serve one training run; returns after `shutdown`.  On error,
        a best-effort `error` control frame carries the traceback to the
        conductor before the exception propagates (→ nonzero exit)."""
        try:
            self._run(ready_queue)
        except Exception as e:
            tb = traceback.format_exc()
            try:
                # etype lets the conductor separate deterministic
                # refusals (never retried) from transient failures
                self.tp.send_control(msg.Control(
                    self.name, CONDUCTOR, kind="error",
                    payload={"party": self.name, "traceback": tb,
                             "etype": type(e).__name__,
                             "peer": getattr(e, "peer", None)}))
            except Exception:                    # noqa: BLE001
                pass
            raise
        finally:
            tp = getattr(self, "tp", None)
            if tp is not None:
                # drain the (possibly shaped) egress pipe first: the
                # last frames out — bye, or the error report above —
                # must actually leave before the sockets die
                try:
                    tp.flush(timeout=self.policy.bye_timeout_s)
                except Exception:                # noqa: BLE001
                    pass
                tp.close()

    def _make_transport(self) -> SocketTransport:
        """Plain socket transport, or the chaos link layer when the
        launcher configured fault injection / wire compression — EVERY
        endpoint of a run must pick the same framing."""
        chaos = self.wire.get("chaos")
        compression = self.wire.get("compression", "none")
        if chaos is None and compression == "none":
            return SocketTransport(self.name, self.codec)
        from repro.runtime.chaos import ChaosProfile, FaultyTransport
        return FaultyTransport(
            self.name, self.codec,
            profile=ChaosProfile.from_dict(chaos),
            policy=self.policy, compression=compression)

    def _run(self, ready_queue) -> None:
        self._listen = socket.create_server((self.host, 0), backlog=32)
        self._listen.settimeout(self.policy.connect_timeout())
        self.port = self._listen.getsockname()[1]
        self.codec = codec_lib.Codec(self._resolve_mod)
        self.tp = self._make_transport()
        # shared request-dispatch core: training and serving both run on
        # it, so infer.wx_share frames cross the same codec/meter stack
        # as training traffic (see runtime/dispatch.py)
        self.core = DispatchCore(self.name, self.tp, self.io_timeout,
                                 deliver=self._dispatch)
        self._opens = self.core.add_stash(
            lambda m: isinstance(m, msg.BeaverOpen), key=lambda m: m.src)
        self._pending_p1 = self.core.add_stash(
            lambda m: isinstance(m, _P1_TYPES) and not self._p1_open)
        self._pending_wx = self.core.add_stash(
            lambda m: isinstance(m, msg.WxShare) and not self._scoring)
        if ready_queue is not None:
            ready_queue.put((self.name, self.port))

        # conductor connects first (parties only learn the roster from
        # its handshake, so no peer can connect before it).
        conn = self._accept()
        hello = self.tp.recv_bootstrap(conn)
        if not (isinstance(hello, msg.Control) and hello.kind == "handshake"):
            raise RuntimeError(f"{self.name}: expected handshake, got "
                               f"{getattr(hello, 'kind', type(hello))}")
        self._apply_handshake(hello.payload)
        self.tp.attach(CONDUCTOR, conn)

        # full party mesh: initiate to lower-index peers (their listeners
        # are up before the conductor handshakes anyone), accept the rest.
        i_self = self.names.index(self.name)
        for peer in self.names[:i_self]:
            s = socket.create_connection(self.roster[peer],
                                         timeout=self.policy
                                         .connect_timeout())
            s.settimeout(self.io_timeout)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self.tp.attach(peer, s)
            self.tp.send_control(msg.Control(self.name, peer, kind="hello"))
        for _ in self.names[i_self + 1:]:
            conn = self._accept()
            first = self.tp.recv_bootstrap(conn)
            if not (isinstance(first, msg.Control) and first.kind == "hello"):
                raise RuntimeError(f"{self.name}: expected hello, got "
                                   f"{getattr(first, 'kind', type(first))}")
            self.tp.attach(first.src, conn)

        self._setup_crypto()
        if self.checkpoint_dir is not None:
            self.ckpt = CheckpointManager(
                self.checkpoint_dir,
                config_hash=session_lib.config_hash(self.cfg),
                codec_version=session_lib.CODEC_VERSION)
        # offer this party's valid, config-compatible checkpoint steps to
        # the conductor's resume handshake (CheckpointMismatch propagates
        # as an `error` control frame — a mismatched resume is REFUSED)
        steps = self.ckpt.steps() if (self.resume and self.ckpt) else []
        self.tp.send_control(msg.Control(self.name, CONDUCTOR, kind="ready",
                                         payload={"ckpt_steps": steps}))
        self._main_loop()

    def _accept(self):
        conn, _ = self._listen.accept()
        conn.settimeout(self.io_timeout)
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return conn

    def _apply_handshake(self, payload: dict) -> None:
        from repro.core.trainer import VFLConfig
        self.names = [r[0] for r in payload["roster"]]
        self.roster = {r[0]: (r[1], int(r[2])) for r in payload["roster"]}
        self.cfg = VFLConfig(**payload["cfg"])
        self.resume = bool(payload.get("resume", False))
        cfg = self.cfg
        self.model = glm_lib.GLMS[cfg.glm]
        self.index = self.names.index(self.name)
        self.n_total = self.X.shape[0]
        self.mask_bound = mask_bound_bits(cfg)
        validate_key_bits(cfg, self.mask_bound)
        # seed-derived stream replicas (registry: runtime/seeds.py)
        self.batch_rng = np.random.default_rng(cfg.seed)
        self.order = self.batch_rng.permutation(self.n_total)
        self.cursor = 0
        self.jkey = jax.random.key(cfg.seed)
        self.dealer = beaver.DealerTripleSource(
            seed=seeds_lib.dealer_seed(cfg.seed))
        self.rng = seeds_lib.party_rng(cfg.seed, self.index)

    def _setup_crypto(self) -> None:
        cfg = self.cfg
        if cfg.he_backend == "mock":
            self.backend = protocols.MockHEBackend(cfg.key_bits)
        else:
            seeds = derive_key_seeds(cfg.seed, self.names)
            own = paillier.keygen(cfg.key_bits, seed=seeds[self.name])
            self.tp.send_control(msg.Control(
                self.name, CONDUCTOR, kind="pubkey",
                payload={"name": self.name, "n": hex(own.pub.n)}))
            roster = self._next_ctrl(expect="pubkeys").payload["keys"]
            keys: dict = {}
            for nm, n_hex in roster.items():
                if nm == self.name:
                    keys[nm] = own
                else:
                    keys[nm] = paillier.PeerKey(paillier.public_key_from_n(
                        int(n_hex, 16), cfg.key_bits))
            self.backend = protocols.PaillierBackend(
                keys, self.rng, engine=engine_mod.make(cfg.crypto_engine))
        if self.name == "C":
            self.actor = LabelParty(self.name, self.X, self.y, cfg,
                                    self.backend, self.rng, self.model)
        else:
            self.actor = DataParty(self.name, self.X, cfg, self.backend,
                                   self.rng)

    def _resolve_mod(self, owner: str):
        """Codec key provider: the key owner's Z_{n²} modulus (None for
        the mock backend → mock ciphertext packing)."""
        if self.backend is None or not hasattr(self.backend, "keys"):
            return None
        return self.backend.keys[owner].pub.mod_n2

    # ------------------------------------------------------------------
    # event loop
    # ------------------------------------------------------------------

    def _dispatch(self, m: msg.Message) -> None:
        if isinstance(m, msg.Flag):
            self._flags_seen += 1
        elif isinstance(m, msg.MaskedGrad):
            self._unmask_served += 1
        self.tp.post_all(self.actor.handle(m) or [])

    def _pump_one(self) -> None:
        self.core.pump_one()

    def _next_ctrl(self, expect: str | None = None) -> msg.Control:
        return self.core.next_ctrl(expect)

    def _main_loop(self) -> None:
        while True:
            c = self._next_ctrl()
            if c.kind == "iter":
                self._run_iteration(int(c.payload["it"]),
                                    tuple(c.payload["cps"]))
            elif c.kind == "resume":
                self._run_resume(int(c.payload["step"]))
            elif c.kind == "score":
                self._run_score(c.payload)
            elif c.kind == "publish":
                self._run_publish(c.payload)
            elif c.kind == "swap":
                self._run_swap(c.payload)
            elif c.kind == "fetch":
                self._run_fetch()
            elif c.kind == "shutdown":
                self.tp.send_control(msg.Control(self.name, CONDUCTOR,
                                                 kind="bye"))
                return
            else:
                raise RuntimeError(f"{self.name}: unknown control "
                                   f"{c.kind!r}")

    # ------------------------------------------------------------------
    # resumable sessions: party-local TrainState slice
    # ------------------------------------------------------------------

    def _capture_state(self, it: int) -> session_lib.TrainState:
        """This party's slice of the step-state machine (see
        runtime/session.py): own weights + own stream positions + own
        meter views.  Never includes another party's weights, any share,
        or any private key material."""
        tp = self.tp
        return session_lib.TrainState(
            it=int(it),
            weights={self.name: np.array(self.actor.W, np.float64)},
            losses=[float(v) for v in getattr(self.actor, "losses", [])],
            stop=bool(self.actor.stop),
            order=np.asarray(self.order, np.int64),
            cursor=int(self.cursor),
            batch_rng=seeds_lib.generator_state(self.batch_rng),
            jkey=np.asarray(jax.random.key_data(self.jkey)),
            protocol_rng=self.rng.state(),
            select_rng=None,               # CP selection is conductor-owned
            dealer=self.dealer.state(),
            noise_pool_fill=0,             # no prefetch pool on this path
            meter_sends=session_lib.LedgerView(tp.meter.sends),
            rounds=int(tp.rounds),
            runtime_s=0.0,
            measured_sends=session_lib.LedgerView(tp.measured.sends),
            overhead_bytes=int(tp.overhead_bytes),
            frames_sent=int(tp.frames_sent))

    def _restore_state(self, st: session_lib.TrainState) -> None:
        """In-place restore: the HE backend's rng handle aliases
        `self.rng`, so the mask/noise stream position propagates."""
        self.actor.W = np.array(st.weights[self.name], np.float64)
        self.actor.stop = bool(st.stop)
        if self.name == "C":
            self.actor.losses = [float(v) for v in st.losses]
        seeds_lib.restore_generator(self.batch_rng, st.batch_rng)
        self.order = np.asarray(st.order, np.int64)
        self.cursor = int(st.cursor)
        self.jkey = jax.random.wrap_key_data(
            jnp.asarray(np.asarray(st.jkey, np.uint32)))
        self.rng.set_state(st.protocol_rng)
        self.dealer.set_state(st.dealer)
        tp = self.tp
        tp.meter = session_lib.rebuild_meter(st.meter_sends)
        tp.measured = session_lib.rebuild_meter(st.measured_sends or [])
        tp.overhead_bytes = int(st.overhead_bytes)
        tp.frames_sent = int(st.frames_sent)
        tp.rounds = int(st.rounds)

    def _save_checkpoint(self, step: int) -> None:
        tree, extra = self._capture_state(step).to_checkpoint()
        self.ckpt.save(step, tree, extra)

    def _run_resume(self, step: int) -> None:
        """Roll back to the cluster-agreed common step (0 = fresh start)
        and report the audited stream positions; the conductor asserts
        the replicated counters (dealer draws, batch cursor, iteration)
        agree across all k parties before training continues."""
        if step > 0:
            if self.ckpt is None:
                raise RuntimeError(f"{self.name}: resume to step {step} "
                                   "without a checkpoint directory")
            got = self.ckpt.restore(
                session_lib.TrainState.tree_template([self.name]),
                step=step)
            if got is None:
                raise RuntimeError(
                    f"{self.name}: agreed resume step {step} is missing "
                    "or invalid in this party's checkpoint directory")
            _, tree, extra = got
            self._restore_state(
                session_lib.TrainState.from_checkpoint(tree, extra))
        audit = {"party": self.name, "step": int(step),
                 "dealer_drawn": int(self.dealer.drawn),
                 "cursor": int(self.cursor),
                 "rng_drawn": int(self.rng.drawn())}
        if self.name == "C":
            audit.update(losses=[float(v) for v in self.actor.losses],
                         stop=bool(self.actor.stop))
        self.tp.send_control(msg.Control(self.name, CONDUCTOR,
                                         kind="resume_ok", payload=audit))

    # ------------------------------------------------------------------
    # one Algorithm-1 iteration
    # ------------------------------------------------------------------

    def _leg_opener(self, peer: str):
        """Network opener for `mpc.pairwise.PairLeg`: ship (d,e) halves
        as one stacked `beaver_open` frame, then pump until the peer's
        matching frame arrives (TCP keeps per-connection order, and the
        legs are in program lockstep, so the next open from `peer` is
        THE matching one)."""
        def opener(d_self: R64, e_self: R64):
            import jax.numpy as jnp
            both = R64(jnp.stack([d_self.hi, e_self.hi]),
                       jnp.stack([d_self.lo, e_self.lo]))
            n = int(np.prod(d_self.lo.shape)) if d_self.lo.shape else 1
            self.tp.post(msg.BeaverOpen(self.name, peer, both,
                                        n_elems=2 * n))
            while not self._opens[peer]:
                self._pump_one()
            m = self._opens[peer].popleft()
            d_peer = R64(m.payload.hi[0], m.payload.lo[0])
            e_peer = R64(m.payload.hi[1], m.payload.lo[1])
            return (ring.add(d_self, d_peer), ring.add(e_self, e_peer))
        return opener

    def _leg_triples(self, cp_index: int):
        def triples(shape):
            self._dealer_draws += 1
            return self.dealer.elementwise(shape)[cp_index]
        return triples

    def _run_iteration(self, it: int, cps: tuple[str, str]) -> None:
        cfg, tp, party, names = self.cfg, self.tp, self.actor, self.names
        k = len(names)
        model = self.model
        # batch schedule — replicated from VFLScheduler.run
        if self.cursor + cfg.batch_size > self.n_total:
            self.order = self.batch_rng.permutation(self.n_total)
            self.cursor = 0
        idx = self.order[self.cursor:self.cursor + cfg.batch_size]
        self.cursor += cfg.batch_size
        nb = len(idx)
        self.jkey, *subkeys = jax.random.split(self.jkey, k * 2 + 3)
        party.begin_iteration(idx, cps, nb, self.mask_bound)
        self._flags_seen = 0
        self._unmask_served = 0
        self._dealer_draws = 0
        is_cp = self.name in cps
        self._p1_open = is_cp
        if is_cp:
            while self._pending_p1:        # shares that beat the iter frame
                self._dispatch(self._pending_p1.popleft())
        elif self._pending_p1:
            raise RuntimeError(f"{self.name}: Protocol-1 share addressed "
                               "to a non-CP")

        # -- Protocol 1: post this party's shares ------------------------
        tp.post_all(party.share_z(subkeys[self.index]))
        if self.name == "C":
            tp.post_all(party.share_y(subkeys[k]))
        if model.needs_exp:
            tp.post_all(party.share_ez(subkeys[k + 1 + self.index],
                                       model.exp_sign))

        noncps = [n for n in names if n not in cps]
        expected_muls = glm_lib.joint_muls_per_iteration(cfg.glm, k)
        if is_cp:
            cpi = cps.index(self.name)
            peer = cps[1 - cpi]
            expect_p1 = k + 1 + (k if model.needs_exp else 0)
            while party.cp.n_p1 < expect_p1:
                self._pump_one()
            self._p1_open = False

            leg = pairwise.PairLeg(cpi, self._leg_triples(cpi),
                                   self._leg_opener(peer))
            ez = None
            if model.needs_exp:
                ez = glm_lib.ez_chain_leg(leg, party.cp.ez_ordered(names),
                                          cfg.f)
            ctx = glm_lib.LegCtx(z=party.cp.z_acc, y=party.cp.y_share,
                                 ez=ez, f=cfg.f)
            # -- Protocol 2 + 3 ------------------------------------------
            party.cp.d_self = model.gradient_leg(leg, ctx)
            tp.post(party.announce_enc_d())
            tp.post_all(party.broadcast_enc_d(noncps))
            # -- Protocol 4 ----------------------------------------------
            # (dealer order matches the local scheduler: chain + gradient
            # muls, then loss muls; Protocol 3 draws nothing)
            party.cp.l_self = model.loss_leg(leg, ctx)
            if cpi == 1:
                tp.post(msg.LossShare(self.name, cps[0], party.cp.l_self,
                                      n_elems=1))
            if self._dealer_draws != expected_muls:
                raise RuntimeError(
                    f"{self.name}: drew {self._dealer_draws} Beaver "
                    f"triples, stream model says {expected_muls} — dealer "
                    "replicas would desynchronize")
        else:
            self.dealer.skip(expected_muls)

        # -- completion: weights updated; C reveals loss + flags.  A CP
        # additionally drains all k-1 decrypt obligations (one MaskedGrad
        # per other party) BEFORE acking: the durable checkpoint below
        # snapshots the send ledger, and an UnmaskedShare reply serviced
        # after the snapshot would vanish from the meters if this step
        # ever becomes a resume point.
        owed = (k - 1) if is_cp else 0
        if self.name == "C":
            while party._pending_unmask or len(party.losses) < it + 1 \
                    or self._unmask_served < owed:
                self._pump_one()
            tp.post_all(party.emit_flags([n for n in names if n != "C"]))
        else:
            while party._pending_unmask or not self._flags_seen \
                    or self._unmask_served < owed:
                self._pump_one()
        # durable checkpoint BEFORE the ack: once the conductor's barrier
        # sees every party's iter_done for a cadence step, every party
        # has the step on disk (a crash mid-save leaves a torn file the
        # loader skips, so the previous cadence step wins the handshake)
        step = it + 1
        if self.ckpt is not None and self.cfg.checkpoint_every \
                and step % self.cfg.checkpoint_every == 0:
            self._save_checkpoint(step)
        done = {"it": it}
        if self.name == "C":
            done.update(loss=party.losses[-1], stop=bool(party.stop))
        tp.send_control(msg.Control(self.name, CONDUCTOR, kind="iter_done",
                                    payload=done))

    # ------------------------------------------------------------------
    # serving + result collection
    # ------------------------------------------------------------------

    def _run_score(self, payload: dict) -> None:
        """Serving path over the same wire: each party ships its local
        score share X_p W_p to C as an `infer.wx_share` frame; C sums
        in roster order and applies the inverse link.

        With a `version` in the payload the share is computed against
        that PUBLISHED version's pinned weights; a party whose serving
        cache disagrees (version or key fingerprint) refuses with
        `StaleCacheError` — a deterministic refusal the conductor never
        retries — instead of silently scoring the wrong model."""
        rows = np.asarray(payload["rows"], np.float64)
        version = payload.get("version")
        if self.name != "C":
            self.tp.post(self.actor.wx_share_msg(rows, dst="C",
                                                 version=version))
            return
        self._scoring = True
        self.actor.begin_inference(rows.shape[0],
                                   [n for n in self.names if n != "C"])
        while self._pending_wx:            # shares that beat the score frame
            self._dispatch(self._pending_wx.popleft())
        while not self.actor.inference_ready:
            self._pump_one()
        preds = self.actor.finish_inference(rows, version=version)
        self._scoring = False
        self.tp.send_control(msg.Control(
            self.name, CONDUCTOR, kind="score_result",
            payload={"rid": payload.get("rid"), "preds": preds.tolist(),
                     "version": version}))

    def _run_publish(self, payload: dict) -> None:
        """Pin the actor's CURRENT weights as served model `version` and
        build the per-version serving cache (windowed digits + encrypted
        constant — repro/serve/cache.py)."""
        v = int(payload["version"])
        self.actor.publish_version(v)
        self.tp.send_control(msg.Control(
            self.name, CONDUCTOR, kind="publish_ok",
            payload={"party": self.name, "version": v,
                     "key_fp": self.actor.serving_cache.key_fp}))

    def _run_swap(self, payload: dict) -> None:
        """Hot-model-swap barrier leg: load this party's OWN TrainState
        slice from the agreed checkpoint step and republish it as the
        new version.  The conductor's engine only issues `swap` with no
        batch in flight, and every subsequent `score` frame carries the
        new version, so no batch is ever scored by mixed versions (a
        straggler party would refuse via the version check above)."""
        step, v = int(payload["step"]), int(payload["version"])
        if self.ckpt is None:
            raise RuntimeError(f"{self.name}: hot swap to step {step} "
                               "without a checkpoint directory")
        got = self.ckpt.restore(
            session_lib.TrainState.tree_template([self.name]), step=step)
        if got is None:
            raise RuntimeError(
                f"{self.name}: swap step {step} is missing or invalid "
                "in this party's checkpoint directory")
        _, tree, extra = got
        st = session_lib.TrainState.from_checkpoint(tree, extra)
        self.actor.set_weights(st.weights[self.name], version=v)
        self.tp.send_control(msg.Control(
            self.name, CONDUCTOR, kind="swap_ok",
            payload={"party": self.name, "version": v, "step": step,
                     "key_fp": self.actor.serving_cache.key_fp}))

    def _run_fetch(self) -> None:
        dump = {
            "party": self.name,
            "weights": np.asarray(self.actor.W, np.float64).tolist(),
            "sends": [[s.src, s.dst, s.tag, s.nbytes]
                      for s in self.tp.meter.sends],
            "measured": [[s.src, s.dst, s.tag, s.nbytes]
                         for s in self.tp.measured.sends],
            "overhead_bytes": self.tp.overhead_bytes,
            "frames_sent": self.tp.frames_sent,
        }
        stats = getattr(self.tp, "chaos_stats", None)
        if stats is not None:
            dump["chaos"] = stats.to_dict()
        if self.name == "C":
            dump["losses"] = [float(v) for v in self.actor.losses]
        self.tp.send_control(msg.Control(self.name, CONDUCTOR,
                                         kind="result", payload=dump))


def run_party_server(name: str, X, y, ready_queue, platform: str,
                     host: str = "127.0.0.1",
                     checkpoint_dir: str | None = None,
                     wire: dict | None = None) -> None:
    """Spawn entry point (multiprocessing 'spawn' target).  Pins JAX to
    `platform` before the process's first JAX computation, so a party
    never reaches for a chip another process holds."""
    import sys

    from repro.launch import compile_cache
    os.environ["JAX_PLATFORMS"] = platform
    jax.config.update("jax_platforms", platform)
    compile_cache.enable()
    print(f"party {name}: jax platform {platform}", file=sys.stderr,
          flush=True)
    PartyServer(name, X, y=y, host=host,
                checkpoint_dir=checkpoint_dir, wire=wire).run(ready_queue)
