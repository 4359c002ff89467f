"""The training cell's comparison: a sound run is correct; a step that
returns its state unchanged, or one that leaves out half of the batch and
takes the mean over the rest, is not. (One chip: no exchange to leave
out.)"""

CELL = "mamba2-370m.train_stream"


def test_sound_run_is_correct(run_small):
    out = run_small(CELL, 2**31 + 77)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


def test_state_left_unchanged_is_caught(run_small):
    def fault(trainer):
        from repro.runtime.train_loop import make_train_step
        step = make_train_step(trainer.model, trainer.opt_cfg, donate=False)

        def unchanged(params, opt_state, batch, i):
            return (params, opt_state) + (step(params, opt_state, batch, i)[2],)

        trainer._step_fn = unchanged

    out = run_small(CELL, 5, fault)
    assert not out["correct"]
    assert out["checks"]["update_leaf_gap"]["value"] > out["checks"]["update_leaf_gap"]["limit"]


def test_half_the_batch_is_caught(run_small):
    def fault(trainer):
        step = trainer._step_fn

        def half(params, opt_state, batch, i):
            rows = batch["tokens"].shape[0] // 2
            return step(params, opt_state, {"tokens": batch["tokens"][:rows]}, i)

        trainer._step_fn = half

    out = run_small(CELL, 6, fault)
    assert not out["correct"]
