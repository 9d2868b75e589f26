"""The share legs send no host array to the device behind the program's
back: the logistic loss and gradient operator, the Poisson loss, the
k = 3 e^z chain, a CP's local gradient term and the key owner's
offset-corrected decryption, on the mock backend, under a process-wide
guard that refuses implicit host→device transfers (leg 1 of a joint
evaluation runs on its own thread, which a thread-local guard would not
reach)."""
import contextlib

import jax
import numpy as np
import pytest

from repro.core import glm, protocols
from repro.crypto import fixed_point
from repro.mpc import beaver, sharing

F, FX, WIDTH, N, M = 14, 10, 22, 48, 5


@contextlib.contextmanager
def no_implicit_transfers():
    prev = jax.config.jax_transfer_guard_host_to_device
    jax.config.update("jax_transfer_guard_host_to_device", "disallow")
    try:
        yield
    finally:
        jax.config.update("jax_transfer_guard_host_to_device", prev)


def _shares(x, seed):
    return sharing.share(fixed_point.encode(x, F), jax.random.key(seed))


def _legs():
    rng = np.random.default_rng(3)
    z = _shares(rng.normal(size=N) * 0.5, 1)
    y_pm = _shares(np.sign(rng.normal(size=N)), 2)
    y_cnt = _shares(rng.poisson(1.0, size=N).astype(np.float64), 3)
    ez = [_shares(np.exp(rng.normal(size=N) * 0.2), 10 + p)
          for p in range(3)]
    feats = protocols.EncodedFeatures.make(rng.normal(size=(N, M)), FX,
                                           WIDTH)
    masked = _shares(rng.normal(size=M), 20)[0]
    mock = protocols.MockHEBackend()

    def lr(op):
        return lambda dealer: op(glm.ShareCtx(z=z, y=y_pm, ez=None, f=F,
                                              dealer=dealer))

    def pr_loss(dealer):
        return glm.POISSON.loss_shares(glm.ShareCtx(
            z=z, y=y_cnt, ez=ez[0], f=F, dealer=dealer))

    return {
        "lr_loss": lr(glm.LOGISTIC.loss_shares),
        "lr_grad_op": lr(glm.LOGISTIC.gradient_operator),
        "pr_loss": pr_loss,
        "ez_chain_k3": lambda dealer: glm.ez_chain_pair(ez, F, dealer),
        "local_grad_share": lambda _: protocols.local_grad_share(feats, z[0]),
        "decrypt_offset_corrected":
            lambda _: protocols.decrypt_offset_corrected(
                mock, "C", masked, z[1], WIDTH),
    }


LEGS = sorted(_legs())


@pytest.mark.parametrize("leg", LEGS)
def test_share_leg_makes_no_implicit_transfer(leg):
    """Run once to compile, then again, from a dealer made beforehand
    (as a party makes its dealer in set-up), under the guard."""
    run = _legs()[leg]
    warm = jax.block_until_ready(run(beaver.DealerTripleSource(5)))
    dealer = beaver.DealerTripleSource(5)
    with no_implicit_transfers():
        again = jax.block_until_ready(run(dealer))
    for a, b in zip(jax.tree.leaves(warm), jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
