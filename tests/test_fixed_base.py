"""Persistent fixed-base tables: build/eval correctness and persistence
hardening (torn writes, key mismatches, stale layouts)."""
import os

import numpy as np
import pytest

import jax.numpy as jnp

from repro.crypto import bigint, fixed_base, paillier
from repro.crypto import engine as engine_mod
from repro.crypto.bigint import Modulus

KEY_BITS = 128
ENG = engine_mod.CryptoEngine(backend="jnp")


@pytest.fixture(scope="module")
def keypair():
    return paillier.keygen(KEY_BITS, seed=11)


@pytest.fixture(scope="module")
def table(keypair):
    return fixed_base.build_noise_table(
        keypair.pub.n, keypair.pub.mod_n2, window=4, rho_bits=64,
        rng=np.random.default_rng(5))


# ---------------------------------------------------------------------------
# build + eval
# ---------------------------------------------------------------------------

def test_build_shapes_and_header(keypair, table):
    assert table.purpose == "noise"
    assert table.exp_bits == 64 and table.levels == 16
    assert table.table_rns.shape[:2] == (16, 16)
    h = table.header()
    assert h["fingerprint"] == fixed_base.key_fingerprint(keypair.pub.n)
    assert h["limb_bits"] == 12 and h["L"] == keypair.pub.mod_n2.L


def test_eval_matches_pow_oracle(keypair, table):
    pub = keypair.pub
    n2 = pub.mod_n2.value
    R = 1 << (12 * pub.mod_n2.L)
    exps = [0, 1, (1 << 64) - 1, 0x1234ABCD]
    digits = fixed_base.exp_digits(exps, table.levels, table.window)
    out = np.asarray(ENG.fixed_base_exp(table, digits, pub.mod_n2))
    for e, row in zip(exps, out):
        want = (pow(table.base, e, n2) * R) % n2
        assert paillier.decode_ints(row)[0] == want


def test_table_goes_to_the_device_once(keypair):
    """Every evaluation reads the one device copy of the table; after it
    is made, an evaluation sends only its digits."""
    from repro import obs
    pub = keypair.pub
    fresh = fixed_base.build_noise_table(
        pub.n, pub.mod_n2, window=4, rho_bits=64,
        rng=np.random.default_rng(5))
    digits = fixed_base.draw_exponent_digits(fresh, 3,
                                             np.random.default_rng(2))
    first = np.asarray(ENG.fixed_base_exp(fresh, digits, pub.mod_n2))
    resident = fresh.device_rns
    before = obs.snapshot()["h2d_bytes"]
    again = np.asarray(ENG.fixed_base_exp(fresh, digits, pub.mod_n2))
    assert obs.snapshot()["h2d_bytes"] - before == digits.nbytes
    assert fresh.device_rns is resident
    np.testing.assert_array_equal(np.asarray(resident), fresh.table_rns)
    np.testing.assert_array_equal(again, first)


def test_draw_digits_uniform_shape(table):
    d = fixed_base.draw_exponent_digits(table, 7, np.random.default_rng(1))
    assert d.shape == (7, table.levels) and d.dtype == np.uint32
    assert d.max() < 1 << table.window


def test_table_noise_decrypts(keypair, table):
    """h^ρ is valid encryption noise: Enc(m; table-noise) decrypts to m."""
    pub = keypair.pub
    digits = fixed_base.draw_exponent_digits(table, 3,
                                             np.random.default_rng(2))
    rn = paillier.noise_from_table(pub, table, digits, ENG)
    m = paillier.encode_ints(pub, [0, 42, pub.n - 1])
    ct = paillier.encrypt_with_noise(pub, m, rn, ENG)
    dec = paillier.decode_ints(np.asarray(paillier.decrypt_crt(
        keypair, ct, engine=ENG)))
    assert dec == [0, 42, pub.n - 1]


def test_generator_table(keypair):
    pub = keypair.pub
    g = 1 + pub.n
    t = fixed_base.build_generator_table(pub.n, g, pub.mod_n2,
                                         window=4, msg_bits=16)
    n2 = pub.mod_n2.value
    R = 1 << (12 * pub.mod_n2.L)
    digits = fixed_base.exp_digits([777], t.levels, 4)
    out = np.asarray(ENG.fixed_base_exp(t, digits, pub.mod_n2))
    assert paillier.decode_ints(out[0])[0] == (pow(g, 777, n2) * R) % n2


# ---------------------------------------------------------------------------
# persistence hardening
# ---------------------------------------------------------------------------

def test_save_load_roundtrip(keypair, table, tmp_path):
    path = str(tmp_path / "noise.npz")
    fixed_base.save_table(table, path)
    assert not any(".tmp." in f for f in os.listdir(tmp_path))  # atomic
    back = fixed_base.load_table(path, n=keypair.pub.n,
                                 mod=keypair.pub.mod_n2, window=4)
    assert back.base == table.base and back.levels == table.levels
    np.testing.assert_array_equal(back.table_rns, table.table_rns)


def test_load_rejects_other_key(keypair, table, tmp_path):
    path = str(tmp_path / "noise.npz")
    fixed_base.save_table(table, path)
    other = paillier.keygen(KEY_BITS, seed=99)
    with pytest.raises(fixed_base.TableMismatchError, match="fingerprint"):
        fixed_base.load_table(path, n=other.pub.n, mod=other.pub.mod_n2)


def test_load_rejects_stale_layout(keypair, table, tmp_path):
    """A table whose window/layout no longer matches the requested
    configuration is a MISMATCH (stale file), not corruption."""
    path = str(tmp_path / "noise.npz")
    fixed_base.save_table(table, path)
    with pytest.raises(fixed_base.TableMismatchError, match="window"):
        fixed_base.load_table(path, n=keypair.pub.n,
                              mod=keypair.pub.mod_n2, window=8)


def test_load_rejects_torn_file(keypair, table, tmp_path):
    """Truncation anywhere in the file → TableCorruptError, never a
    silently wrong table (and never TableMismatchError)."""
    path = str(tmp_path / "noise.npz")
    fixed_base.save_table(table, path)
    blob = open(path, "rb").read()
    for cut in (10, len(blob) // 2, len(blob) - 7):
        with open(path, "wb") as f:
            f.write(blob[:cut])
        with pytest.raises(fixed_base.TableCorruptError):
            fixed_base.load_table(path, n=keypair.pub.n,
                                  mod=keypair.pub.mod_n2)


def test_load_rejects_bit_rot(keypair, table, tmp_path):
    """Payload digest catches content damage an intact zip would hide."""
    import io, zipfile
    path = str(tmp_path / "noise.npz")
    fixed_base.save_table(table, path)
    # rewrite the npz with one payload byte flipped but valid zip structure
    src = zipfile.ZipFile(path)
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w") as dst:
        for name in src.namelist():
            data = src.read(name)
            if name == "table_rns.npy":
                data = data[:-1] + bytes([data[-1] ^ 1])
            dst.writestr(name, data)
    with open(path, "wb") as f:
        f.write(buf.getvalue())
    with pytest.raises(fixed_base.TableCorruptError, match="digest"):
        fixed_base.load_table(path, n=keypair.pub.n,
                              mod=keypair.pub.mod_n2)


def test_ensure_table_builds_loads_rebuilds(keypair, tmp_path):
    pub = keypair.pub
    path = str(tmp_path / "noise.npz")
    t1, built1 = fixed_base.ensure_table(pub.n, pub.mod_n2, path,
                                         rho_bits=64,
                                         rng=np.random.default_rng(3))
    t2, built2 = fixed_base.ensure_table(pub.n, pub.mod_n2, path,
                                         rho_bits=64)
    assert built1 and not built2
    np.testing.assert_array_equal(t1.table_rns, t2.table_rns)
    # corrupt the file: ensure_table rebuilds instead of failing
    with open(path, "wb") as f:
        f.write(b"garbage")
    t3, built3 = fixed_base.ensure_table(pub.n, pub.mod_n2, path,
                                         rho_bits=64,
                                         rng=np.random.default_rng(4))
    assert built3
    fixed_base.load_table(path, n=pub.n, mod=pub.mod_n2)  # now valid again


def test_keygen_table_path_attach(tmp_path):
    path = str(tmp_path / "noise.npz")
    priv = paillier.keygen(KEY_BITS, seed=21, table_path=path)
    assert priv.noise_table is not None
    assert os.path.exists(path)
    # backend auto-attaches and the mismatch guard works
    from repro.core import protocols
    backend = protocols.PaillierBackend({"A": priv},
                                        np.random.default_rng(1), ENG)
    assert "A" in backend.tables
    other = paillier.keygen(KEY_BITS, seed=22)
    backend2 = protocols.PaillierBackend({"B": other},
                                         np.random.default_rng(1), ENG)
    with pytest.raises(fixed_base.TableMismatchError):
        backend2.attach_table("B", priv.noise_table)


# ---------------------------------------------------------------------------
# End-to-end: table noise trains the bit-identical model
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_train_table_noise_parity(tmp_path):
    """End-to-end Algorithm 1 with real Paillier: routing encryption
    noise through persistent fixed-base tables trains the bit-identical
    model to the r^n ladder — masks cancel exactly and noise never
    reaches a decrypted value, so the noise *source* is model-invisible."""
    from repro.core import trainer
    from repro.data import synthetic, vertical

    X, y = synthetic.credit_default(n=60, d=4, seed=3)
    parts = vertical.split_columns(X, 2)
    parties = [trainer.PartyData(name=nm, X=p)
               for nm, p in zip(["C", "B1"], parts)]
    cfg = trainer.VFLConfig(glm="logistic", lr=0.1, max_iter=1,
                            batch_size=16, he_backend="paillier",
                            key_bits=256, tol=0.0, seed=2,
                            crypto_engine="jnp")
    names = [p.name for p in parties]
    ref_backend = trainer.make_backend(cfg, names, np.random.default_rng(9))
    ref = trainer.train_vfl(parties, y, cfg, backend=ref_backend)

    tab_backend = trainer.make_backend(cfg, names, np.random.default_rng(9))
    for nm in names:                       # same keys (same rng seed)
        assert tab_backend.keys[nm].pub.n == ref_backend.keys[nm].pub.n
        pub = tab_backend.keys[nm].pub
        tbl, built = fixed_base.ensure_table(
            pub.n, pub.mod_n2, str(tmp_path / f"noise_{nm}.npz"),
            rho_bits=96, rng=np.random.default_rng(31))
        assert built
        tab_backend.attach_table(nm, tbl)
    res = trainer.train_vfl(parties, y, cfg, backend=tab_backend)

    assert set(tab_backend.tables) == set(names)   # table path was live
    assert res.losses == ref.losses
    for name in ref.weights:
        np.testing.assert_array_equal(res.weights[name], ref.weights[name])
