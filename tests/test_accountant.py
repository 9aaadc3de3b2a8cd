"""RDPAccountant's per-(q, sigma) curve cache: the composed RDP and every
epsilon are bit-identical to recomputing the curve on each step."""
import numpy as np
import pytest

from repro.core.accountant import (
    CURVE_CACHE_SIZE,
    RDPAccountant,
    eps_from_rdp,
    rdp_subsampled_gaussian,
)
from repro.core.engine import PrivacyEngine
from repro.policies import QuantilePolicy

SAMPLE_SIZE = 50_000
# the benchmark cells' batches over CIFAR-10's 50,000: q = 0.02048 (VGG19),
# 0.00256 (BEiT-large), 4e-5 (xLSTM-350M, 2 sequences)
BATCHES = (1024, 128, 2)


def _loss(params, batch, ctx):
    raise NotImplementedError  # accounting only


def _engine(batch_size: int, release_sigma: float | None) -> PrivacyEngine:
    policy = None if release_sigma is None else QuantilePolicy(release_sigma=release_sigma)
    return PrivacyEngine(
        loss_with_ctx=_loss, batch_size=batch_size, sample_size=SAMPLE_SIZE,
        steps=100, max_grad_norm=1.0, noise_multiplier=1.0, clip_policy=policy,
    )


def _recomputed(engine: PrivacyEngine, n: int) -> np.ndarray:
    """n steps composed as before the cache: each step's curves freshly
    computed, gradient mechanism then release, one addition each."""
    alphas = engine.accountant.alphas
    rdp = np.zeros(len(alphas))
    for _ in range(n):
        for s in (engine.noise_multiplier, *engine._release_sigmas()):
            rdp = rdp + rdp_subsampled_gaussian(engine.sampling_rate, s, alphas)
    return rdp


@pytest.mark.parametrize("n", [1, 7, 64])
@pytest.mark.parametrize("release_sigma", [None, 0.4], ids=["fixed", "quantile"])
@pytest.mark.parametrize("batch_size", BATCHES, ids=["vgg", "beit", "xlstm"])
def test_cached_composition_is_bit_identical(batch_size, release_sigma, n):
    engine = _engine(batch_size, release_sigma)
    for _ in range(n):
        engine.record_step()
    ref = _recomputed(engine, n)
    acc = engine.accountant
    assert np.array_equal(acc._rdp, ref)
    delta = engine.target_delta
    assert acc.get_epsilon(delta) == eps_from_rdp(ref, acc.alphas, delta)[0]
    assert acc.curve_evals == (1 if release_sigma is None else 2)
    assert acc.steps_composed == n * (1 if release_sigma is None else 2)


@pytest.mark.parametrize("release_sigma", [None, 0.4], ids=["fixed", "quantile"])
def test_curve_evaluated_once_per_key(release_sigma):
    engine = _engine(1024, release_sigma)
    acc = engine.accountant
    keys = 1 if release_sigma is None else 2
    for _ in range(1000):
        engine.record_step()
    assert acc.curve_evals == keys
    assert acc.steps_composed == 1000 * keys
    engine.noise_multiplier = 1.5
    engine.record_step()
    assert acc.curve_evals == keys + 1


def test_curve_map_is_bounded_and_read_only():
    acc = RDPAccountant()
    q = 0.02048
    sigmas = [0.5 + 0.1 * i for i in range(CURVE_CACHE_SIZE + 5)]
    for s in sigmas + sigmas[:3]:  # the first three were evicted: computed again
        curve = acc.curve(q, s)
        assert np.array_equal(curve, rdp_subsampled_gaussian(q, s, acc.alphas))
        assert len(acc._curves) <= CURVE_CACHE_SIZE
    assert len(acc._curves) == CURVE_CACHE_SIZE
    assert acc.curve_evals == len(sigmas) + 3
    # the keys used last stay: sigmas[-1] was not evicted
    acc.curve(q, sigmas[-1])
    assert acc.curve_evals == len(sigmas) + 3
    with pytest.raises(ValueError):
        curve[0] = 0.0


@pytest.mark.parametrize("release_sigma", [None, 0.4], ids=["fixed", "quantile"])
def test_resume_replay_matches_stepwise(release_sigma):
    stepwise, replayed = _engine(1024, release_sigma), _engine(1024, release_sigma)
    for _ in range(5000):
        stepwise.record_step()
    replayed.record_step(5000)
    assert np.array_equal(replayed.accountant._rdp, stepwise.accountant._rdp)
    delta = stepwise.target_delta
    assert replayed.accountant.get_epsilon(delta) == stepwise.accountant.get_epsilon(delta)
    keys = 1 if release_sigma is None else 2
    assert replayed.accountant.curve_evals == stepwise.accountant.curve_evals == keys
