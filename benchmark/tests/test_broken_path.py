"""A run whose timed path is broken underneath comes out with ``correct``
false.  Only the harness's look for a chip is skipped; the rest of a run
is driven as `run.py` drives it."""

def _run(tiny_cell, name, seed=5):
    from benchmark import cellrun

    return cellrun.run_cell(name, seed, 1.0, False, allow_cpu=True,
                            cell=tiny_cell(name))


def test_sound_runs_are_correct(tiny_cell):
    assert _run(tiny_cell, "tiny-train")["correct"]
    assert _run(tiny_cell, "tiny-serve")["correct"]


def test_step_that_returns_its_state_unchanged(tiny_cell, monkeypatch):
    from distributed_deep_learning_tpu.workloads import base

    real = base.make_train_eval_steps

    def make(*args, **kw):
        train_step, eval_step = real(*args, **kw)

        def stuck(state, x, y):
            # the loss is computed, the update is dropped
            return state, {"loss": eval_step(state, x, y)["loss"]}

        return stuck, eval_step

    monkeypatch.setattr(base, "make_train_eval_steps", make)
    assert not _run(tiny_cell, "tiny-train")["correct"]


def test_step_that_leaves_out_part_of_the_batch(tiny_cell, monkeypatch):
    from distributed_deep_learning_tpu.workloads import base

    real = base.make_train_eval_steps

    def make(*args, **kw):
        train_step, eval_step = real(*args, **kw)

        def half(state, x, y):
            n = x.shape[0] // 2     # second half a copy of the first
            x = x.at[n:].set(x[:n])
            y = y.at[n:].set(y[:n])
            return train_step(state, x, y)

        return half, eval_step

    monkeypatch.setattr(base, "make_train_eval_steps", make)
    assert not _run(tiny_cell, "tiny-train")["correct"]


def test_token_altered_where_it_is_produced(tiny_cell, monkeypatch):
    from distributed_deep_learning_tpu.serve.engine import PagedEngine

    real = PagedEngine._sample

    def off_by_one(self, params, hidden_last, key):
        toks, lp, ok = real(self, params, hidden_last, key)
        return (toks + 1) % self.model.vocab_size, lp, ok

    monkeypatch.setattr(PagedEngine, "_sample", off_by_one)
    assert not _run(tiny_cell, "tiny-serve")["correct"]
