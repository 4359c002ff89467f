"""The device hop's telemetry: named scopes mapped onto the compiled
program's HLO ops (``repro.runtime.op_scopes``), and the trainer's and
loader's spans and counters on the process tracer."""
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.core import ConsumerGroup, PartitionedLog, make_flowfile, telemetry
from repro.core.sources import corpus_documents
from repro.data import StreamingDataLoader
from repro.models import Model
from repro.optim import OptConfig
from repro.runtime import Trainer, TrainerConfig, op_scopes, tracing


@pytest.mark.parametrize("op_name, scope", [
    ("jit(step)/transpose(jvp(layer_stack))/while/body/closed_call/"
     "checkpoint/rematted_computation/ssd/exp", "ssd"),
    ("jit(step)/transpose(jvp(layer_stack))/while/body/dynamic_update_slice",
     "layer_stack"),
    ("jit(step)/jvp(layer_stack)/while/body/closed_call/mixer_proj/"
     "ssd/jit(ssd)/mul", "ssd"),                       # innermost wins
    ("jit(step)/transpose(jvp(head))/mul;jit(step)/transpose(jvp(head))/"
     "broadcast_in_dim", "head"),
    ("jit(step)/add_any;jit(step)/optimizer/sub", "optimizer"),
    ("jit(step)/optimizer/sub", "optimizer"),
    ("jit(step)/reduce_sum", "unscoped"),
    ("jit(step)/ssd", "unscoped"),            # a primitive, not a scope
    ("", "unscoped"),
])
def test_scope_of(op_name, scope):
    assert tracing.scope_of(op_name) == scope


def test_hlo_op_scopes_falls_back_to_the_called_computation():
    hlo = "\n".join([
        "HloModule m",
        "",
        "%fused_computation.1 (p: f32[4]) -> f32[4] {",
        "  %p = f32[4]{0} parameter(0)",
        '  ROOT %exp.1 = f32[4]{0} exponential(%p), '
        'metadata={op_name="jit(f)/layer_stack/ssd/exp"}',
        "}",
        "",
        "ENTRY %main.2 (a: f32[4]) -> f32[4] {",
        "  %a = f32[4]{0} parameter(0)",
        "  %fusion.3 = f32[4]{0} fusion(%a), kind=kLoop, "
        "calls=%fused_computation.1",
        '  ROOT %add.4 = f32[4]{0} add(%fusion.3, %a), '
        'metadata={op_name="jit(f)/transpose(jvp(head))/add"}',
        "}",
    ])
    table = tracing.hlo_op_scopes(hlo)
    assert table["fusion.3"] == "ssd"          # from its computation's root
    assert table["add.4"] == "head"
    assert table["a"] == "unscoped"


def test_hlo_mixed_scopes_lists_ops_whose_members_differ():
    hlo = "\n".join([
        "HloModule m",
        "",
        "%fused_computation.1 (p: f32[4]) -> f32[4] {",
        "  %p = f32[4]{0} parameter(0)",
        '  %sl.1 = f32[4]{0} negate(%p), '
        'metadata={op_name="jit(f)/layer_stack/while/body/dynamic_slice"}',
        '  ROOT %exp.1 = f32[4]{0} exponential(%sl.1), '
        'metadata={op_name="jit(f)/layer_stack/ssd/exp"}',
        "}",
        "",
        "%fused_computation.2 (q: f32[4]) -> f32[4] {",
        "  %q = f32[4]{0} parameter(0)",
        '  ROOT %log.2 = f32[4]{0} log(%q), '
        'metadata={op_name="jit(f)/layer_stack/ssd/log"}',
        "}",
        "",
        "ENTRY %main.3 (a: f32[4]) -> f32[4] {",
        "  %a = f32[4]{0} parameter(0)",
        "  %fusion.4 = f32[4]{0} fusion(%a), kind=kLoop, "
        "calls=%fused_computation.1",
        "  %fusion.5 = f32[4]{0} fusion(%fusion.4), kind=kLoop, "
        'calls=%fused_computation.2, metadata={op_name="jit(f)/head/log"}',
        "  ROOT %fusion.6 = f32[4]{0} fusion(%fusion.5), kind=kLoop, "
        "calls=%fused_computation.2",
        "}",
    ])
    mixed = tracing.hlo_mixed_scopes(hlo)
    assert mixed == {"fusion.4": ("layer_stack", "ssd"),
                     "fusion.5": ("head", "ssd")}
    table = tracing.hlo_op_scopes(hlo)
    assert (table["fusion.4"], table["fusion.5"], table["fusion.6"]) == \
        ("ssd", "head", "ssd")


def _tiny_step():
    def layer(h, w):
        with jax.named_scope("mixer_proj"):
            y = h @ w
        with jax.named_scope("ssd"):
            y = jnp.exp(y) * 0.5
        return h + jnp.tanh(y), None

    def loss(p, x):
        with jax.named_scope("embed"):
            h = x * 2
        with jax.named_scope("layer_stack"):
            h, _ = jax.lax.scan(jax.checkpoint(layer), h, p)
        with jax.named_scope("head"):
            return jnp.mean(h ** 2)

    def step(p, x):
        l, g = jax.value_and_grad(loss)(p, x)
        with jax.named_scope("optimizer"):
            p = p - 0.1 * g
        return p, l + jnp.sum(p)               # an op outside every scope

    return jax.jit(step, donate_argnums=(0,))


def test_op_scopes_of_a_scanned_rematted_differentiated_step():
    f = _tiny_step()
    p, x = jnp.full((3, 16, 16), 0.01), jnp.ones((4, 16))
    tracing.register("tiny_step", f, (p, x))
    text = f.lower(p, x).compile().as_text()
    table = op_scopes("tiny_step")
    assert op_scopes("tiny_step") is table       # compiled once
    assert set(table.values()) <= set(tracing.SCOPES) | {tracing.UNSCOPED}
    assert {"embed", "mixer_proj", "ssd", "layer_stack", "head",
            "optimizer", "unscoped"} <= set(table.values())
    # every instruction of the compiled module is in the table
    for line in text.splitlines():
        if line.startswith("  ") and " = " in line:
            name = line.split(" = ", 1)[0].split()[-1].lstrip("%")
            assert name in table, name
    # the exp of the rematted ssd, forward and backward, maps to ssd, not
    # to the enclosing layer_stack; the update to the optimizer
    for line in text.splitlines():
        if 'op_name="' not in line or " = " not in line:
            continue
        name = line.split(" = ", 1)[0].split()[-1].lstrip("%")
        path = line.split('op_name="', 1)[1].split('"', 1)[0]
        if "/ssd/" in path:
            assert table[name] == "ssd", (name, path)
        elif "/optimizer/" in path:
            assert table[name] == "optimizer", (name, path)
    with pytest.raises(KeyError):
        op_scopes("never_registered")
    # an op whose members span scopes is credited to one of them
    mixed = tracing.op_mixed_scopes("tiny_step")
    assert tracing.op_mixed_scopes("tiny_step") is mixed
    for op, members in mixed.items():
        assert len(members) > 1 and table[op] in members, (op, members)


def _trainer(tmp_path, steps=3, log_every=1):
    log = PartitionedLog(tmp_path / "log")
    log.create_topic("corpus", partitions=2)
    for i, doc in enumerate(corpus_documents(400)):
        k, v = make_flowfile(doc, doc_id=str(i)).to_record()
        log.append("corpus", k, v, partition=i % 2)
    member = ConsumerGroup(log, "corpus", "trainer").add_member("host0")
    loader = StreamingDataLoader(member, batch_size=2, seq_len=32)
    model = Model(configs.get_reduced("mamba2-370m"))
    opt = OptConfig(peak_lr=1e-3, warmup_steps=1, total_steps=10)
    tcfg = TrainerConfig(steps=steps, ckpt_every=0, log_every=log_every,
                         ckpt_dir=str(tmp_path / "ck"))
    return log, Trainer(model, loader, opt, tcfg)


def test_trainer_spans_counters_and_step_table(tmp_path):
    tr = telemetry.tracer()
    log, trainer = _trainer(tmp_path)
    lid = trainer.loader.loader_id

    def value(n):
        return tr.value(n) if n == "compiles" else tr.value(n, loader=lid)

    before = {n: value(n) for n in ("loader_batches", "loader_rows",
                                    "loader_tokens", "loader_records",
                                    "loader_bytes", "compiles")}
    t0 = tr.now()
    out = trainer.run()
    assert out["steps"] == 3
    recs = [r for r in tr.spans() if r.t0 >= t0]
    steps = [r for r in recs if r.name == "train/step"]
    assert [r.trace_id for r in steps] == [0, 1, 2]
    by_id = {r.id: r for r in recs}
    for name in ("loader/next_batch", "train/put", "train/dispatch",
                 "train/log_sync"):
        mine = [r for r in recs if r.name == name]
        assert len(mine) == 3, name
        for r in mine:
            parent = by_id[r.parent_id]
            assert parent.name == "train/step"
            assert r.trace_id == parent.trace_id
            assert parent.t0 <= r.t0 <= r.t1 <= parent.t1
    for name in ("loader/poll", "loader/pack"):
        assert {by_id[r.parent_id].name for r in recs if r.name == name} \
            == {"loader/next_batch"}
    assert [r.name for r in recs].count("train/checkpoint") == 1   # the wait
    # the first step compiled: a compile span under its train/dispatch
    compiles = [r for r in recs if r.name == "compile"]
    assert tr.value("compiles") - before["compiles"] >= len(compiles) > 0
    assert any(r.parent_id in by_id and r.trace_id == 0
               and by_id[r.parent_id].name == "train/dispatch"
               for r in compiles)
    after = {n: value(n) for n in before}
    assert after["loader_batches"] - before["loader_batches"] == 3
    rows = after["loader_rows"] - before["loader_rows"]
    assert rows >= 6                           # 3 batches of 2 rows at least
    # rows are cut from the tokens packed (seq 32: 33 tokens a row)
    assert after["loader_tokens"] - before["loader_tokens"] >= rows * 33
    assert after["loader_records"] > before["loader_records"]
    assert after["loader_bytes"] > before["loader_bytes"]
    # one device_get per logged row, every metric a float
    assert len(trainer.history) == 3
    row = trainer.history[-1]
    assert isinstance(row["loss"], float) and isinstance(row["lr"], float)
    assert row["starved_polls"] == value("loader_starved_polls")
    assert trainer.loader.starved_polls == value("loader_starved_polls")
    # the registered step maps its ops onto the model's parts
    table = op_scopes("train_step")
    assert {"embed", "mixer_proj", "conv", "ssd", "gate_norm", "layer_stack",
            "head", "optimizer"} <= set(table.values())
    log.close()


def test_op_scopes_survives_a_replaced_step_fn(tmp_path):
    """Callers may wrap ``_step_fn``; the table still compiles the trainer's
    own jitted step from the shapes recorded before donation."""
    log, trainer = _trainer(tmp_path, steps=1)
    inner = trainer._step_fn
    trainer._step_fn = lambda *a: inner(*a)
    trainer.run()
    assert "ssd" in set(op_scopes("train_step").values())
    log.close()


def test_install_is_idempotent():
    tracing.install()
    tracing.install()
    assert telemetry.tracer()._annotation is tracing._annotation


def _loader_over(log, topic, group):
    member = ConsumerGroup(log, topic, group).add_member("host0")
    return StreamingDataLoader(member, batch_size=2, seq_len=32)


def test_each_loader_counts_its_own_starved_polls(tmp_path):
    log = PartitionedLog(tmp_path / "log")
    log.create_topic("empty", partitions=1)
    first = _loader_over(log, "empty", "a")
    assert first.next_batch(timeout_polls=5) is None
    second = _loader_over(log, "empty", "b")
    assert first.loader_id != second.loader_id
    assert second.starved_polls == 0          # not the first loader's
    assert second.next_batch(timeout_polls=3) is None
    assert (first.starved_polls, second.starved_polls) == (5, 3)
    log.close()


def test_fabric_metrics_show_the_process_spans_and_counters(tmp_path):
    from repro.data.pipeline import build_news_fabric
    fab = build_news_fabric(tmp_path / "fab", workers=1, n_rss=10,
                            n_firehose=10, n_ws=2)
    try:
        log = PartitionedLog(tmp_path / "log")
        log.create_topic("corpus", partitions=1)
        for i, doc in enumerate(corpus_documents(40)):
            k, v = make_flowfile(doc, doc_id=str(i)).to_record()
            log.append("corpus", k, v, partition=0)
        loader = _loader_over(log, "corpus", "trainer")
        assert loader.next_batch() is not None
        text = fab.render_metrics_text()
        assert "repro_fabric_reassignments 0" in text
        lab = f'{{loader="{loader.loader_id}"}}'
        assert f"repro_loader_batches_total{lab} 1" in text
        for name in ("loader_records", "loader_bytes", "loader_tokens",
                     "loader_rows"):
            assert f"repro_{name}_total{lab} " in text, name
        for name in ("loader/next_batch", "loader/poll", "loader/pack"):
            assert f'repro_span_seconds_count{{span="{name}"}} ' in text, name
        log.close()
    finally:
        fab.shutdown(force=True)
        fab.store.close()
