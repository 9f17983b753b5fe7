"""``correct`` at a size a test run can hold: sound runs pass, the control
(the nearest lower precision: the program's own 4-bit pages in serving, the
reference with its gradients rounded to 3 bits in training) does not, and a
timed path broken underneath does not. Every case is a whole run of the harness in
its CPU rehearsal (tiny sizes, the chip gate skipped)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, run as harness, weights

SERVE = "gpt2l-serve-decode"
TRAIN = "gpt2s-dp4-q4"


def rehearse(workload, seed, control=False, devices=1, seconds=2):
    return harness.run(
        ["--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", "0", "--rehearse-cpu", str(devices)]
        + (["--control"] if control else []))


def checks(result):
    return {c["name"]: c for c in result["checks"]}


def test_reference_is_the_programs_model_in_float32():
    from torch_cgx_tpu.models import GPT2, GPT2Config

    cfg = dict(n_layer=2, n_head=4, n_embd=128, n_positions=64,
               vocab_size=512, layer_norm_epsilon=1e-6)
    params = weights.make_params(cfg, 2**31 + 3)
    tokens = np.asarray(weights.make_token_batches(1, 2, 48, 512, 3)[0])
    model = GPT2(GPT2Config(n_layer=2, n_head=4, d_model=128, vocab_size=512,
                            max_seq=64, dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, tokens, train=False)
    want = reference.forward(params, tokens, cfg)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert (jax.tree.structure(params) == jax.tree.structure(
        model.init(jax.random.PRNGKey(0), tokens)["params"]))


def test_weights_are_the_seeds():
    cfg = dict(n_layer=2, n_head=4, n_embd=128, n_positions=64,
               vocab_size=512)
    a = weights.make_params(cfg, 2**31 + 9)
    b = weights.make_params(cfg, 2**31 + 9)
    c = weights.make_params(cfg, 2**31 + 10)
    same = jax.tree.map(lambda x, y: bool(jnp.array_equal(x, y)), a, b)
    assert all(jax.tree.leaves(same))
    assert not bool(jnp.array_equal(a["wte"]["embedding"],
                                    c["wte"]["embedding"]))


def test_worst_leaf_gap_is_a_gap_of_norms_against_leaf_or_median():
    ref = np.array([1.0, 2.0, 1e-9, 4.0])
    got = np.array([1.1, 2.0, 1e-3, 4.0])
    gap, leaf = reference.worst_leaf_gap(got, ref)
    # leaf 0: 0.1 / max(1, median 1.5) ; leaf 2 is all but zero and is
    # held against the median leaf: 1e-3 / 1.5.
    assert leaf == 0 and gap == pytest.approx(0.1 / 1.5)


@pytest.mark.parametrize("seed", [21, 22])
def test_serving_sound_run_is_correct(seed):
    result = rehearse(SERVE, seed)
    assert result["correct"], result["checks"]
    assert result["device"]["platform"] == "cpu"
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {
        "serve_tokens_per_s", "serve_ttft_p90_ms", "serve_itl_p50_ms",
        "setup_s"}


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_serving_control_four_bit_pages_is_not_correct(seed):
    result = rehearse(SERVE, seed, control=True)
    assert not result["correct"], result["checks"]
    assert result["metrics"] == {}  # a control run is no measurement


def test_serving_altered_token_is_not_correct(monkeypatch):
    """A token altered where it is produced: the decode program's greedy
    choice shifted by one."""
    from torch_cgx_tpu.serving import scheduler

    real = scheduler._build_programs

    def broken(server):
        prog = real(server)
        decode = prog.decode_step

        def off_by_one(params, state):
            out, nxt = decode(params, state)
            nxt = (nxt + 1) % server.cfg.vocab_size
            out = dict(out, tokens=jnp.where(out["active"], nxt,
                                             out["tokens"]))
            return out, nxt

        prog.decode_step = off_by_one
        return prog

    scheduler.invalidate_decode_cache("test")
    monkeypatch.setattr(scheduler, "_build_programs", broken)
    try:
        result = rehearse(SERVE, 24)
    finally:
        scheduler.invalidate_decode_cache("test")
    assert not result["correct"], result["checks"]
    assert result["failed"] == 0  # every request still returned its tokens


def test_training_sound_run_is_correct():
    result = rehearse(TRAIN, 31, devices=4, seconds=1)
    assert result["correct"], result["checks"]
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                                "memory_peak_bytes": 0}
    assert set(result["metrics"]) == {"train_step_ms", "setup_s"}


@pytest.mark.parametrize("seed", [31, 32, 33])
def test_training_control_three_bit_gradients_is_not_correct(seed):
    result = rehearse(TRAIN, seed, control=True, devices=4, seconds=1)
    got = checks(result)
    assert not result["correct"], result["checks"]
    assert not got["grad_norm_gap"]["ok"]
    assert result["metrics"] == {} and result["attempted"] == 0


def test_round_to_bits_keeps_the_ends_and_the_stated_levels():
    x = jnp.concatenate([jnp.linspace(-1.0, 1.0, 512),
                         jnp.linspace(0.0, 7.0, 300)]).reshape(4, 203)
    got = reference.round_to_bits(x, 3, 512)
    assert got.shape == x.shape
    flat, kept = np.asarray(x).reshape(-1), np.asarray(got).reshape(-1)
    assert len(np.unique(kept[:512])) == 8 and len(np.unique(kept[512:])) == 8
    assert kept[0] == -1.0 and kept[511] == 1.0 and kept[-1] == 7.0
    assert np.max(np.abs(kept[:512] - flat[:512])) <= 1.0 / 7 + 1e-6
    np.testing.assert_allclose(kept[512:], np.round(flat[512:]), atol=1e-5)
    assert np.array_equal(np.asarray(reference.round_to_bits(
        jnp.full((5,), 2.5), 3, 512)), np.full((5,), 2.5, np.float32))


def test_training_step_that_returns_its_state_is_not_correct(monkeypatch):
    """The timed path broken underneath: a step that returns its state
    unchanged (with a plausible loss)."""
    from torch_cgx_tpu import parallel

    def frozen(loss_fn, opt, mesh, **_):
        def step(params, opt_state, batch, step_idx):
            return params, opt_state, jnp.float32(6.26)
        return step

    monkeypatch.setattr(parallel, "make_train_step", frozen)
    result = rehearse(TRAIN, 34, devices=4, seconds=1)
    got = checks(result)
    assert not result["correct"]
    assert not got["delta_norm_gap"]["ok"]
    assert got["delta_norm_gap"]["value"] == pytest.approx(1.0)


def test_training_part_of_the_batch_left_out_is_not_correct(monkeypatch):
    """Half of every chip's rows dropped before the step sees them."""
    from torch_cgx_tpu import parallel

    real = parallel.make_train_step

    def halved(loss_fn, opt, mesh, **kw):
        return real(lambda p, b: loss_fn(p, b[: b.shape[0] // 2]), opt, mesh,
                    **kw)

    monkeypatch.setattr(parallel, "make_train_step", halved)
    result = rehearse(TRAIN, 35, devices=4, seconds=1)
    assert not result["correct"], result["checks"]
    assert not checks(result)["loss_rel_gap"]["ok"]
