"""Integration: the training launcher end-to-end, incl. resume determinism
and simulated-failure recovery."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.data.pipeline import DataConfig, SyntheticCorpus
from repro.distributed import checkpoint as C
from repro.models import init_params
from repro.runtime import optim as O
from repro.runtime.steps import make_train_step


def _run_steps(cfg, params, opt, step_fn, corpus, start, n):
    losses = []
    for s in range(start, start + n):
        params, opt, m = step_fn(params, opt, corpus.batch(s))
        losses.append(float(m["loss"]))
    return params, opt, losses


def test_resume_is_bitwise_deterministic(tmp_path):
    """train 6 steps straight == train 3, checkpoint, restore, train 3."""
    cfg = get_smoke_config("deepseek-7b")
    oc = O.OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    corpus = SyntheticCorpus(DataConfig(global_batch=2, seq_len=32,
                                        vocab=cfg.vocab))
    step_fn = jax.jit(make_train_step(cfg, oc))

    p0 = init_params(cfg, jax.random.PRNGKey(0))
    o0 = O.init_opt(p0)
    pA, oA, lossA = _run_steps(cfg, p0, o0, step_fn, corpus, 0, 6)

    p1 = init_params(cfg, jax.random.PRNGKey(0))
    o1 = O.init_opt(p1)
    p1, o1, _ = _run_steps(cfg, p1, o1, step_fn, corpus, 0, 3)
    C.save(str(tmp_path), 3, (p1, o1), extra=corpus.cursor(3))
    (p2, o2), step, extra = C.restore(
        str(tmp_path), jax.eval_shape(lambda: (p1, o1)))
    assert step == 3 and extra["step"] == 3
    pB, oB, lossB = _run_steps(cfg, p2, o2, step_fn, corpus, 3, 3)

    for a, b in zip(jax.tree.leaves(pA), jax.tree.leaves(pB)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert abs(lossA[-1] - lossB[-1]) < 1e-6


def test_train_launcher_with_failure_recovery(tmp_path):
    """CLI launcher: checkpoint, simulated device loss, re-mesh, resume."""
    # the CLI turns on the persistent compile cache: keep it out of the
    # checkout
    env = dict(os.environ, PYTHONPATH="src",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "deepseek-7b", "--smoke", "--steps", "8", "--batch", "2",
         "--seq", "32", "--ckpt-dir", str(tmp_path), "--ckpt-every", "4",
         "--simulate-failure-at", "6"],
        capture_output=True, text=True, timeout=560,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "[elastic] simulating failure" in r.stdout
    assert "done:" in r.stdout


def test_grad_compression_training_still_learns():
    """Compressed-grad training converges under a loss-MEDIAN oracle.

    The old check (``mean(losses[-5:]) < losses[0]``) compared a window
    against one arbitrary sample of a noisy series — at smoke scale the
    per-step loss on random tokens swings ~+-0.2, so the test was flaky
    by construction and sat xfail'd.  The sturdier oracle (the
    windowed-median logging idiom from the HomebrewNLP ``wandblog.py``
    exemplar cited in ROADMAP.md) compares the MEDIAN of the first
    window against the median of the last: medians shrug off the
    per-step noise, and the re-tuned run (lr 3e-3, 60 steps — the
    original 20 steps at 1e-3 were simply not enough optimizer work for
    the int8+error-feedback path to show progress) descends ~0.2 nats
    toward the synthetic corpus's ~ln(vocab) entropy floor, several
    times the residual median jitter.
    """
    cfg = get_smoke_config("deepseek-7b")
    oc = O.OptConfig(lr=3e-3, warmup_steps=1, total_steps=70)
    corpus = SyntheticCorpus(DataConfig(global_batch=2, seq_len=32,
                                        vocab=cfg.vocab))
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = O.init_opt(params)
    step_fn = jax.jit(make_train_step(cfg, oc, compress_grads=True))
    _, _, losses = _run_steps(cfg, params, opt, step_fn, corpus, 0, 60)
    assert np.isfinite(losses).all()
    first, last = np.median(losses[:10]), np.median(losses[-10:])
    assert last < first - 0.05, (
        f"loss median did not converge: first10={first:.4f} "
        f"last10={last:.4f}")
