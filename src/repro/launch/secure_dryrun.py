"""EFMVFL on the production mesh: lower + compile the 2-party secure
gradient iteration with  pod = party,  data = sample shards,  model =
feature shards.

This is the paper's protocol as ONE XLA program (DESIGN.md §5): each pod
is an organizational party; within a pod the Protocol-3 hot path
(plaintext-matrix × ciphertext-vector) shards samples over `data` and
feature columns over `model`; the homomorphic ⊕-reduction across sample
shards is the modmul ppermute ladder (psum can't express it).

  PYTHONPATH=src python -m repro.launch.secure_dryrun \
      [--samples 30720] [--features 32] [--key-bits 1024] \
      [--mesh 2x16x16] [--transport local|pipelined|socket]

`--mesh PxDxM` picks the pod×data×model mesh shape (product ≤ the 512
forced host devices), so the same lowering compiles at laptop scale
(`--mesh 2x2x4`) or pod scale; the analytic roofline terms follow the
chosen shape.

`--transport` additionally runs a small *measured* 2-party training
iteration on the chosen runtime transport and reports its per-tag
bytes next to the analytic `protocol_comm` table — with `socket` the
bytes are counted off real encoded TCP frames between party processes
(`runtime.codec` / `launch.cluster`), asserting the analytic table is
what actually crosses the wire.  `--checkpoint-dir` (socket) enables
party-local checkpoints in the measured run and reports the cadence;
adding `--resume` runs the kill-and-resume drill and reports the
`resume_verdict` (docs/fault_tolerance.md).  `--chaos PROFILE`
(socket) routes the measured run through the fault-injection link
layer (`runtime.chaos`) and reports injected faults, ARQ recovery
work, and whether the meters survived bit-exact.

`--tables PATH` builds (or loads) the persistent fixed-base noise table
for a real keypair at `--key-bits` and reports its build time, on-disk
size, and the per-iteration modexp savings of the h^ρ table walk over
the r^n ladder next to the analytic `protocol_comm` table — the
deployment-economics view of docs/engine.md §fixed-base tables.
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse          # noqa: E402
import functools         # noqa: E402
import json              # noqa: E402
import time              # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402
from jax import shard_map                                    # noqa: E402

from repro.crypto.bigint import Modulus, mont_one            # noqa: E402
from repro.crypto import engine as engine_mod                # noqa: E402
from repro.crypto import fixed_point                         # noqa: E402
from repro.crypto.ring import R64                            # noqa: E402
from repro.crypto import ring                                # noqa: E402
from repro.distributed.secure_ops import modmul_reduce       # noqa: E402
from repro.launch import mesh as mesh_lib                    # noqa: E402
from repro.launch.dryrun import (parse_collectives,          # noqa: E402
                                 roofline_terms)
from repro.runtime import messages as msg_lib                # noqa: E402


def montmul_count(n_loc: int, m_loc: int, width: int, window: int,
                  data_size: int) -> float:
    """Analytic Montgomery-product count per device for the secure step
    (XLA counts scan bodies once — see costmodel.py rationale)."""
    if window <= 1:
        return width * (n_loc * m_loc + 2 * m_loc) \
            + m_loc * max(data_size.bit_length() - 1, 0)
    levels = -(-width // window)
    pre = n_loc * ((1 << window) - 2)
    return pre + levels * (n_loc * m_loc + (window + 1) * m_loc) \
        + m_loc * max(data_size.bit_length() - 1, 0)


def flops_per_montmul(L: int) -> float:
    """CIOS: L rounds × (2 MAC rows + lazy carries) ≈ 8·L² int32 ops."""
    return 8.0 * L * L


def make_secure_grad_step(mesh, mod: Modulus, width: int, window: int = 1,
                          shard_mode: str = "feature", engine=None):
    """Builds the jitted 2-party Protocol-3 step.

    Global shapes (pod-major):
      exps   (2, n, m)  uint32 — per-party offset-lifted fixed-point X
      cts    (2, n, L2) uint32 — [[⟨d⟩_other]] under the other party's key
      d_hi/lo(2, n)     uint32 — own share ⟨d⟩_self (ring 2^64)
    Returns per-party (2, m, L2) encrypted masked gradients + (2, m)
    ring shares of the local term X^T⟨d⟩_self.
    window=1: bit-serial (paper-faithful baseline); window=4: fixed-window
    ladder (§Perf optimized variant, ~3.6× fewer Montgomery products).
    `engine` routes the Montgomery products through the crypto compute
    engine — the same dispatch the trainer/runtime hits — so `--engine
    pallas` lowers the step with the fused kernels inside the shard_map.
    Default None = the jnp library (keeps the XLA cost model exact).
    """
    eng = engine if engine is not None \
        else engine_mod.CryptoEngine(backend="jnp")

    def mont_mul(a, b, m):
        return eng.mont_mul(a, b, m)

    data_size = mesh.shape["data"]
    model_size = mesh.shape["model"]
    L2 = mod.L
    # feature mode: samples/data, features/model (m_loc = m/16 — small
    # window-table amortization).  sample2d mode: samples over BOTH axes
    # (n_loc = n/256), features replicated — the table amortizes fully and
    # the ⊕-ladder runs over both axes (4+4 hops).
    sample_axes = ("data",) if shard_mode == "feature" else ("data", "model")

    def _tree(c):
        while c.shape[0] > 1:
            half = c.shape[0] // 2
            merged = mont_mul(c[:half], c[half:2 * half], mod)
            if c.shape[0] % 2:
                merged = jnp.concatenate([merged, c[2 * half:]], 0)
            c = merged
        return c[0]

    samp = sample_axes if len(sample_axes) > 1 else sample_axes[0]
    feat = "model" if shard_mode == "feature" else None
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P("pod", samp, feat), P("pod", samp, None),
                  P("pod", samp), P("pod", samp)),
        out_specs=(P("pod", feat, None), P("pod", feat)),
        check_vma=False)
    def step(exps, cts, d_hi, d_lo):
        exps = exps[0]                       # (n_loc, m_loc) this party
        cts = cts[0]                         # (n_loc, L2)
        acc0 = jnp.broadcast_to(mont_one(mod), (exps.shape[1], L2))
        if window <= 1:
            # --- bit-serial ladder (baseline) ----------------------------
            bits = fixed_point.int_bits_msb(exps, width)     # (n, m, w)
            one = jnp.broadcast_to(mont_one(mod), cts.shape)

            def bit_step(acc, bits_t):
                acc = mont_mul(acc, acc, mod)
                sel = jnp.where(bits_t[..., None] == 1, cts[:, None, :],
                                one[:, None, :])
                return mont_mul(acc, _tree(sel), mod), None

            acc, _ = jax.lax.scan(bit_step, acc0,
                                  jnp.moveaxis(bits, -1, 0))
        else:
            # --- fixed-window ladder (§Perf variant) ---------------------
            levels = -(-width // window)
            digs = jnp.stack(
                [(exps >> ((levels - 1 - lv) * window))
                 & ((1 << window) - 1) for lv in range(levels)], axis=-1)
            table = [jnp.broadcast_to(mont_one(mod), cts.shape), cts]
            for _ in range(2, 1 << window):
                table.append(mont_mul(table[-1], cts, mod))
            table = jnp.stack(table, 0)

            def win_step(acc, d_lvl):
                for _ in range(window):
                    acc = mont_mul(acc, acc, mod)
                sel = jnp.take_along_axis(
                    table[:, :, None, :], d_lvl[None, :, :, None],
                    axis=0)[0]
                return mont_mul(acc, _tree(sel), mod), None

            acc, _ = jax.lax.scan(win_step, acc0,
                                  jnp.moveaxis(digs, -1, 0))
        # cross-shard ⊕-reduce over the sample axis/axes (modmul ladder)
        enc_g = modmul_reduce(acc, mod, "data", data_size)
        if shard_mode == "sample2d":
            enc_g = modmul_reduce(enc_g, mod, "model", model_size)

        # --- local ring term X^T ⟨d⟩_self (additive across sample shards:
        # a native psum — contrast with the ⊕ ladder above).  Z_2^64 sums
        # cross shards via 16-bit-split psums so carries survive in u32.
        d_self = R64(d_hi[0], d_lo[0])
        x_signed = (exps.astype(jnp.int32) - (1 << (width - 1)))
        g_loc = ring.matmul(x_signed.T,
                            R64(d_self.hi[:, None], d_self.lo[:, None]))
        lo, hi = g_loc.lo[:, 0], g_loc.hi[:, 0]
        p0 = jax.lax.psum(lo & jnp.uint32(0xFFFF), sample_axes)
        p1 = jax.lax.psum(lo >> 16, sample_axes)
        q0 = jax.lax.psum(hi & jnp.uint32(0xFFFF), sample_axes)
        q1 = jax.lax.psum(hi >> 16, sample_axes)
        mid = (p0 >> 16) + p1
        g_lo = (p0 & jnp.uint32(0xFFFF)) | (mid << 16)
        carry = mid >> 16
        g_hi = q0 + (q1 << 16) + carry
        return enc_g[None], jnp.stack([g_hi, g_lo], -1)[None]

    return step


def measured_comm(transport: str, features: int, key_bits: int,
                  samples: int = 256, checkpoint_dir: str | None = None,
                  resume_drill: bool = False,
                  chaos: str | None = None) -> dict:
    """One *measured* 2-party training iteration on a runtime transport.

    Mirrors the analytic `protocol_comm` shape (2 parties, `features`
    features EACH, fixed CP selection, mock HE at `key_bits`) at a
    reduced batch so the dry-run stays fast, and compares the per-tag
    bytes the transport actually metered against the analytic
    `iteration_traffic` synthesis for the same shape.  With `socket`
    the run spans real OS processes and the bytes are measured off the
    encoded TCP frames (plus the frame-overhead total the analytic
    table deliberately excludes).

    With `checkpoint_dir` (socket only) the run trains 2 iterations at
    `checkpoint_every=1` and records the party-local checkpoint cadence;
    `resume_drill` additionally SIGKILLs B1 mid-run, lets the supervisor
    resume from the checkpoints, and reports whether the recovered run
    is bit-identical to an uninterrupted single-process reference — the
    `resume_verdict` column of the dry-run table.

    `chaos` (socket only) names a `runtime.chaos.PROFILES` entry and
    runs the measured iteration through the fault-injection link layer
    (`FaultyTransport`): the report gains a `chaos` block with injected
    fault counts, ARQ recovery work (retransmits, backoff), and a
    `chaos_verdict` — `recovered_bit_exact` iff the per-tag meters
    still equal the analytic table despite the injected faults
    (docs/fault_tolerance.md §chaos).
    """
    import numpy as np
    from repro.core.trainer import PartyData, VFLConfig, train_vfl
    from repro.runtime.scheduler import min_key_bits
    from repro.runtime.transport import LocalTransport, PipelinedTransport

    nb = min(samples, 256)
    rng = np.random.default_rng(0)
    X = rng.normal(size=(nb, 2 * features)) * 0.3
    y = (rng.random(nb) < 0.5).astype(np.float64) * 2 - 1
    parties = [PartyData("C", X[:, :features]),
               PartyData("B1", X[:, features:])]
    checkpointing = checkpoint_dir is not None and transport == "socket"
    n_iter = 2 if checkpointing else 1
    cfg = VFLConfig(glm="logistic", lr=0.1, max_iter=n_iter, batch_size=nb,
                    he_backend="mock", key_bits=key_bits, tol=0.0, seed=0,
                    checkpoint_every=1 if checkpointing else 0)
    # a LIVE iteration needs a key that can carry its masked values; the
    # analytic lowering has no such floor (e.g. the documented 128-bit
    # compile check), so bump the measured run to the minimum viable key
    # and record it — the analytic comparison below uses the same size.
    key_bits = max(key_bits, min_key_bits(cfg))
    cfg.key_bits = key_bits
    out = {"transport": transport, "iterations": n_iter, "batch": nb,
           "features_per_party": features, "key_bits": key_bits}
    if transport == "socket":
        # party processes must not inherit the 512 forced host devices
        from repro.launch.cluster import (train_vfl_socket,
                                          train_vfl_socket_resilient)
        saved = os.environ.get("XLA_FLAGS")
        os.environ["XLA_FLAGS"] = (saved or "").replace(
            "--xla_force_host_platform_device_count=512", "").strip()
        try:
            if checkpointing and resume_drill:
                res = train_vfl_socket_resilient(
                    parties, y, cfg, checkpoint_dir=checkpoint_dir,
                    kill_plan={1: "B1"}, chaos=chaos)
            else:
                res = train_vfl_socket(parties, y, cfg,
                                       checkpoint_dir=checkpoint_dir,
                                       chaos=chaos)
        finally:
            if saved is not None:
                os.environ["XLA_FLAGS"] = saved
        out["measured_mb_by_tag"] = {
            k: v / 1e6 for k, v in sorted(res.measured_meter.by_tag.items())}
        out["frame_overhead_mb"] = res.wire_overhead_bytes / 1e6
        measured = dict(res.measured_meter.by_tag)
        if checkpointing:
            from repro.checkpoint import party_checkpoint_dir, valid_steps
            from repro.runtime import session
            ck = {"dir": checkpoint_dir, "every": cfg.checkpoint_every,
                  "steps_on_disk": {
                      p.name: valid_steps(
                          party_checkpoint_dir(checkpoint_dir, p.name),
                          expect_config_hash=session.config_hash(cfg),
                          expect_codec_version=session.CODEC_VERSION)
                      for p in parties}}
            if resume_drill:
                ref = train_vfl(parties, y, cfg,
                                transport=LocalTransport())
                identical = (res.losses == ref.losses
                             and dict(res.meter.by_tag)
                             == dict(ref.meter.by_tag)
                             and all(np.array_equal(res.weights[n],
                                                    ref.weights[n])
                                     for n in ref.weights))
                ck.update(restarts=getattr(res, "restarts", 0),
                          resume_step=res.resume_report.get("step"),
                          resume_verdict=("bit_identical" if identical
                                          else "DIVERGED"))
            out["checkpoint"] = ck
    else:
        tp = {"local": LocalTransport,
              "pipelined": PipelinedTransport}[transport]()
        res = train_vfl(parties, y, cfg, transport=tp)
        out["measured_mb_by_tag"] = {
            k: v / 1e6 for k, v in sorted(res.meter.by_tag.items())}
        measured = dict(res.meter.by_tag)
    analytic, _ = msg_lib.iteration_traffic(
        n_parties=2, nb=nb, m_per_party=features, key_bits=key_bits)
    out["matches_analytic"] = measured == {
        k: v * res.n_iter for k, v in analytic.items()}
    report = getattr(res, "chaos_report", None)
    if report is not None:
        t = report["total"]
        out["chaos"] = {
            "profile": chaos,
            "injected": {k: t.get(k, 0) for k in
                         ("drops", "dups", "reorders", "resets",
                          "partitions")},
            "retransmits": t.get("retransmits", 0),
            "rx_dups": t.get("rx_dups", 0),
            "backoff_total_s": round(t.get("backoff_total_s", 0.0), 3),
            "chaos_verdict": ("recovered_bit_exact"
                              if out["matches_analytic"] else "DIVERGED"),
        }
    return out


def serving_report(k: int = 3, n_req: int = 48, batch: int = 8) -> dict:
    """One in-process serving micro-run for the dry-run report: train a
    tiny k-party GLM, serve `n_req` requests through the continuous-
    batching scoring engine (`serve.VFLScoringEngine`, docs/serving.md)
    and report p50/p99 latency, throughput, and the serving wire
    identity — metered `infer.wx_share` bytes must equal the analytic
    n_req·(k−1)·8 — plus a hot-swap drill verdict: served predictions
    at the published version must be bit-identical to the one-shot
    scorer."""
    import numpy as np
    from repro.core import glm as glm_lib
    from repro.core.trainer import PartyData, VFLConfig
    from repro.data import synthetic, vertical
    from repro.runtime import VFLScheduler
    from repro.serve import VFLScoringEngine

    X, y = synthetic.credit_default(n=160, d=8, seed=23)
    parts = vertical.split_columns(X, k)
    names = ["C"] + [f"B{i}" for i in range(1, k)]
    parties = [PartyData(nm, p) for nm, p in zip(names, parts)]
    cfg = VFLConfig(glm="logistic", lr=0.1, max_iter=2, batch_size=128,
                    he_backend="mock", tol=0.0, seed=23)
    sched = VFLScheduler(parties, y, cfg)
    res = sched.run()
    eng = VFLScoringEngine(sched.parties, max_batch=batch)
    for i in range(n_req):
        eng.submit({nm: part[i % part.shape[0]]
                    for nm, part in zip(names, parts)})
    done = sorted(eng.run(), key=lambda r: r.rid)
    lat = eng.latencies()
    got = np.array([r.prediction for r in done])
    want = glm_lib.GLMS[cfg.glm].predict(res.predict_wx(parties))[:n_req]
    wx_bytes = eng.transport.meter.by_tag["infer.wx_share"]
    return {
        "parties": k, "n_req": n_req, "max_batch": batch,
        "model_version": eng.model_version,
        "p50_ms": round(float(np.percentile(lat, 50) * 1e3), 4),
        "p99_ms": round(float(np.percentile(lat, 99) * 1e3), 4),
        "wx_share_bytes": int(wx_bytes),
        "wx_share_bytes_analytic": n_req * (k - 1) * 8,
        "wire_ok": int(wx_bytes) == n_req * (k - 1) * 8,
        "serve_verdict": ("bit_identical"
                          if np.array_equal(got, want) else "DIVERGED"),
    }


def tables_report(path: str, key_bits: int, engine_name: str,
                  nb: int, m: int) -> dict:
    """Fixed-base noise-table economics for a REAL keypair at
    `key_bits`: one-time build cost + on-disk size of the persistent
    table (`crypto/fixed_base.py`), and the measured per-noise modexp
    cost of the h^ρ table walk vs the r^n library ladder, scaled to one
    iteration's noise demand (nb ciphertexts for the CP's [[⟨d⟩]] + m
    for the non-CP masked-matvec leg) — the column that sits next to
    the analytic `protocol_comm` table."""
    import numpy as np
    from repro.crypto import fixed_base, paillier

    t0 = time.time()
    key = paillier.keygen(key_bits, seed=0)
    keygen_s = time.time() - t0
    pub = key.pub
    t0 = time.time()
    table, built = fixed_base.ensure_table(pub.n, pub.mod_n2, path,
                                           rng=np.random.default_rng(1))
    build_s = time.time() - t0
    eng = engine_mod.make(engine_name)
    rng = np.random.default_rng(2)
    batch = 4
    ladder = jax.jit(lambda rr: paillier.noise_to_mont(pub, rr, eng))
    raw = jnp.asarray(paillier.raw_noise(pub, batch, rng))
    jax.block_until_ready(ladder(raw))            # compile
    t0 = time.time()
    jax.block_until_ready(ladder(raw))
    ladder_us = (time.time() - t0) * 1e6 / batch
    digits = jnp.asarray(fixed_base.draw_exponent_digits(table, batch, rng))
    jax.block_until_ready(
        paillier.noise_from_table(pub, table, digits, eng))
    t0 = time.time()
    jax.block_until_ready(
        paillier.noise_from_table(pub, table, digits, eng))
    table_us = (time.time() - t0) * 1e6 / batch
    noise_per_iter = nb + m                       # k=2: CP nb + one leg m
    return {
        "path": path, "built_now": built, "engine": engine_name,
        "key_bits": key_bits,
        "keygen_s": round(keygen_s, 2),
        "build_s": round(build_s, 2),
        "bytes_on_disk": os.path.getsize(path),
        "window": table.window, "levels": table.levels,
        "exp_bits": table.exp_bits,
        "ladder_us_per_noise": round(ladder_us, 1),
        "table_us_per_noise": round(table_us, 1),
        "speedup": round(ladder_us / table_us, 1),
        "noise_terms_per_iteration": noise_per_iter,
        "modexp_savings_per_iteration_s": round(
            noise_per_iter * (ladder_us - table_us) / 1e6, 2),
    }


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--samples", type=int, default=30720)
    ap.add_argument("--features", type=int, default=32)
    ap.add_argument("--key-bits", type=int, default=1024)
    ap.add_argument("--width", type=int, default=22)
    ap.add_argument("--window", type=int, default=1,
                    help="1 = paper-faithful bit-serial; 4 = §Perf variant")
    ap.add_argument("--shard-mode", default="feature",
                    choices=("feature", "sample2d"))
    ap.add_argument("--engine", default="jnp",
                    choices=("jnp", "pallas-interpret", "pallas"),
                    help="crypto compute engine for the Montgomery "
                         "products (jnp keeps the cost model exact)")
    ap.add_argument("--tables", default=None, metavar="PATH",
                    help="build/load the persistent fixed-base noise "
                         "table for a real keypair at --key-bits and "
                         "report build time, on-disk size, and the "
                         "per-iteration modexp savings of h^ρ table "
                         "walks vs the r^n ladder (docs/engine.md "
                         "§fixed-base tables)")
    ap.add_argument("--mesh", default="2x16x16",
                    help="pod×data×model mesh shape, e.g. 2x16x16 "
                         "(pod = party; product ≤ 512)")
    ap.add_argument("--transport", default="none",
                    choices=("none", "local", "pipelined", "socket"),
                    help="also run one measured training iteration on "
                         "this runtime transport (socket = real "
                         "processes over TCP) and report measured "
                         "per-tag bytes next to the analytic table")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="(socket transport) enable party-local "
                         "checkpoints at every iteration in the "
                         "measured run and report the cadence")
    ap.add_argument("--resume", action="store_true",
                    help="(with --checkpoint-dir) kill a party mid-run, "
                         "resume via the supervisor, and report the "
                         "resume verdict (bit_identical | DIVERGED)")
    from repro.runtime.chaos import PROFILES
    ap.add_argument("--chaos", default=None, choices=sorted(PROFILES),
                    help="(socket transport) run the measured iteration "
                         "through the fault-injection link layer with "
                         "this runtime.chaos profile and report injected "
                         "faults, ARQ recovery work, and the chaos "
                         "verdict next to the measured comm table")
    ap.add_argument("--serve", action="store_true",
                    help="also run an in-process serving micro-report "
                         "(continuous-batching scoring engine): p50/p99 "
                         "latency, throughput, the infer.wx_share wire "
                         "identity, and the served-vs-one-shot verdict "
                         "(docs/serving.md)")
    ap.add_argument("--out", default="results/secure_dryrun.json")
    args = ap.parse_args()

    if args.checkpoint_dir and args.transport != "socket":
        raise SystemExit("--checkpoint-dir needs --transport socket "
                         "(party-local checkpoints live in the party "
                         "processes)")
    if args.resume and not args.checkpoint_dir:
        raise SystemExit("--resume needs --checkpoint-dir")
    if args.chaos and args.transport != "socket":
        raise SystemExit("--chaos needs --transport socket (the fault-"
                         "injection link layer wraps the TCP transport)")
    try:
        dims = tuple(int(v) for v in args.mesh.lower().split("x"))
        assert len(dims) == 3 and all(d >= 1 for d in dims)
    except (ValueError, AssertionError):
        raise SystemExit(f"--mesh must be PxDxM (got {args.mesh!r})")
    n_dev = len(jax.devices())
    if int(jnp.prod(jnp.asarray(dims))) > n_dev:
        raise SystemExit(f"--mesh {args.mesh} needs {dims[0]*dims[1]*dims[2]}"
                         f" devices; only {n_dev} forced host devices")
    # the shard_map in_specs and the analytic roofline both assume exact
    # divisibility — fail loudly instead of reporting a zeroed roofline
    d_sz_, m_sz_ = dims[1], dims[2]
    pow2_axes = (d_sz_,) if args.shard_mode == "feature" else (d_sz_, m_sz_)
    if any(s & (s - 1) for s in pow2_axes):
        raise SystemExit(f"--mesh {args.mesh}: the homomorphic ⊕-ladder "
                         "(modmul_reduce butterfly) needs power-of-two "
                         "sample-shard axes (data; also model in "
                         "sample2d mode)")
    samp_div = d_sz_ if args.shard_mode == "feature" else d_sz_ * m_sz_
    if args.samples % samp_div:
        raise SystemExit(f"--samples {args.samples} must be a multiple of "
                         f"the sample shard factor {samp_div} (mesh "
                         f"{args.mesh}, shard-mode {args.shard_mode})")
    if args.shard_mode == "feature" and args.features % m_sz_:
        raise SystemExit(f"--features {args.features} must be a multiple "
                         f"of the model axis size {m_sz_} in feature "
                         "shard-mode")
    mesh = mesh_lib._make_mesh(dims, ("pod", "data", "model"))
    # a real key size's modulus shape — value content irrelevant for
    # lowering, but Modulus wants a genuine odd modulus for its constants
    mod = Modulus.make((1 << (2 * args.key_bits)) - 159)
    step = make_secure_grad_step(mesh, mod, args.width, args.window,
                                 args.shard_mode,
                                 engine=engine_mod.make(args.engine))

    n, m, L2 = args.samples, args.features, mod.L
    u32 = jnp.uint32
    # the [[⟨d⟩]] operand is exactly the runtime's P3.enc_d envelope,
    # lowered pod-major (pod axis = party); locals (exps, own d-share)
    # never cross the transport and are plain arrays.
    enc_d_spec = msg_lib.EncD.mesh_payload_spec(2, n, L2)
    specs = (
        jax.ShapeDtypeStruct((2, n, m), u32),
        enc_d_spec,
        jax.ShapeDtypeStruct((2, n), u32),
        jax.ShapeDtypeStruct((2, n), u32),
    )
    in_shardings = (
        NamedSharding(mesh, P("pod", "data", "model")),
        NamedSharding(mesh, P("pod", "data", None)),
        NamedSharding(mesh, P("pod", "data")),
        NamedSharding(mesh, P("pod", "data")),
    )
    t0 = time.time()
    lowered = jax.jit(step, in_shardings=in_shardings).lower(*specs)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    colls = parse_collectives(compiled.as_text())
    # analytic roofline terms (HLO counts scan bodies once) — per-device
    # local sizes follow the chosen mesh shape
    d_sz, m_sz = mesh.shape["data"], mesh.shape["model"]
    if args.shard_mode == "feature":
        n_loc, m_loc, ladder = n // d_sz, max(m // m_sz, 1), d_sz
    else:
        n_loc, m_loc, ladder = n // (d_sz * m_sz), m, d_sz * m_sz
    mm = montmul_count(n_loc, m_loc, args.width, args.window, ladder)
    flops = mm * flops_per_montmul(L2)
    # HBM: ciphertext block re-read per ladder level + exps + outputs
    levels = args.width if args.window <= 1 else -(-args.width
                                                   // args.window)
    hbm = (n_loc * L2 * 4) * levels + n_loc * m_loc * 4
    coll = m_loc * L2 * 4 * max(d_sz.bit_length() - 1, 0)  # ⊕-ladder hops
    # per-iteration cross-party traffic, synthesized from the same typed
    # Message envelopes the live runtime routes (comm columns + rounds)
    by_tag, rounds = msg_lib.iteration_traffic(
        n_parties=2, nb=n, m_per_party=m, key_bits=args.key_bits)
    res = {
        "kind": "secure_efmvfl_grad_step",
        "mesh": args.mesh, "key_bits": args.key_bits,
        "engine": args.engine,
        "samples": n, "features": m, "exp_width": args.width,
        "window": args.window, "shard_mode": args.shard_mode,
        "montmuls_per_dev": mm,
        "compile_s": round(time.time() - t0, 1),
        "peak_bytes_per_dev": ma.peak_memory_in_bytes,
        "flops_per_dev": flops,
        "hbm_bytes_per_dev": float(hbm),
        "raw_hlo": {"flops": float(ca.get("flops", 0.0)),
                    "bytes": float(ca.get("bytes accessed", 0.0))},
        "collectives": colls,
        "protocol_comm": {
            "per_iteration_mb_by_tag": {k: v / 1e6
                                        for k, v in sorted(by_tag.items())},
            "per_iteration_rounds": rounds,
        },
        **roofline_terms(flops, float(hbm), float(coll)),
        "ok": True,
    }
    if args.tables:
        res["fixed_base_tables"] = tables_report(
            args.tables, args.key_bits, args.engine, nb=n, m=m)
    if args.transport != "none":
        res["measured_comm"] = measured_comm(
            args.transport, m, args.key_bits,
            checkpoint_dir=args.checkpoint_dir,
            resume_drill=args.resume, chaos=args.chaos)
    if args.serve:
        res["serving"] = serving_report()
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps({k: v for k, v in res.items()
                      if k not in ("collectives",)}, indent=1))
    print("collective ops:", res["collectives"]["op_counts"])


if __name__ == "__main__":
    main()
