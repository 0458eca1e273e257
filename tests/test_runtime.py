"""Runtime substrate tests: data pipeline, checkpointing, optimizer,
gradient compression, supervisor fault tolerance, serving."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dev dep: property tests skip, the rest run
    from _hypothesis_stub import given, settings, st

from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data.pipeline import SyntheticLM
from repro.models.model_zoo import build
from repro.optim import adamw, compression
from repro.optim.adamw import AdamWConfig
from repro.runtime.serve_loop import Server
from repro.runtime.supervisor import InjectedFailure, Supervisor
from repro.runtime.train_loop import (Trainer, init_train_state,
                                      make_train_step)


# ------------------------------------------------------------------- data ----

def test_data_deterministic_and_host_sharded():
    a = SyntheticLM(100, 16, 8, n_hosts=2, host_id=0, seed=3)
    b = SyntheticLM(100, 16, 8, n_hosts=2, host_id=1, seed=3)
    x0 = a.batch_at(5)["tokens"]
    x0_again = SyntheticLM(100, 16, 8, n_hosts=2, host_id=0,
                           seed=3).batch_at(5)["tokens"]
    np.testing.assert_array_equal(x0, x0_again)
    assert x0.shape == (4, 17)
    assert not np.array_equal(x0, b.batch_at(5)["tokens"])  # disjoint shards


def test_data_checkpoint_resume():
    d = SyntheticLM(50, 8, 4, seed=1)
    for _ in range(3):
        next(d)
    state = d.state_dict()
    ref = next(d)["tokens"]
    d2 = SyntheticLM(50, 8, 4, seed=1)
    d2.load_state_dict(state)
    np.testing.assert_array_equal(next(d2)["tokens"], ref)


@settings(max_examples=20, deadline=None)
@given(step=st.integers(0, 10_000), vocab=st.integers(2, 65536))
def test_data_tokens_in_range(step, vocab):
    d = SyntheticLM(vocab, 8, 2, seed=0)
    t = d.batch_at(step)["tokens"]
    assert t.min() >= 0 and t.max() < vocab


# -------------------------------------------------------------- checkpoint ----

def test_checkpoint_roundtrip_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)},
             "opt": {"step": jnp.int32(4)}}
    for step in (1, 2, 3):
        mgr.save(step, state, extra={"data_step": step})
    assert mgr.all_steps() == [2, 3]  # keep=2 GC'd step 1
    step, restored, extra = mgr.restore(state)
    assert step == 3 and extra["data_step"] == 3
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.arange(6.0).reshape(2, 3))


def test_checkpoint_async_and_atomic(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    state = {"w": jnp.ones((128, 128))}
    mgr.save(7, state, async_save=True)
    mgr.wait()
    assert mgr.latest_step() == 7
    # no stray temp dirs after publish
    assert not [d for d in os.listdir(tmp_path) if d.startswith(".tmp_")]


def test_checkpoint_elastic_restore(tmp_path):
    """A checkpoint written under one mesh restores onto another (here: the
    1-device host mesh with explicit shardings) — the elastic path."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_host_mesh
    mgr = CheckpointManager(str(tmp_path))
    state = {"w": jnp.arange(16.0).reshape(4, 4)}
    mgr.save(1, state)
    mesh = make_host_mesh()
    sh = {"w": NamedSharding(mesh, P("data", "model"))}
    _, restored, _ = mgr.restore(state, shardings=sh)
    assert restored["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(16.0).reshape(4, 4))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100))
def test_checkpoint_property_roundtrip(tmp_path_factory, seed):
    rng = np.random.default_rng(seed)
    tree = {"a": rng.standard_normal((3, 5)).astype(np.float32),
            "nested": {"b": rng.integers(0, 9, (4,)).astype(np.int32)}}
    mgr = CheckpointManager(str(tmp_path_factory.mktemp("ck")))
    mgr.save(seed, tree)
    _, restored, _ = mgr.restore(tree, step=seed)
    for k in ("a",):
        np.testing.assert_array_equal(np.asarray(restored[k]), tree[k])
    np.testing.assert_array_equal(np.asarray(restored["nested"]["b"]),
                                  tree["nested"]["b"])


# -------------------------------------------------------------------- optim ----

def test_adamw_converges_quadratic():
    cfg = AdamWConfig(lr=0.1, warmup_steps=0, total_steps=200,
                      weight_decay=0.0, grad_clip=10.0)
    params = {"w": jnp.array([5.0, -3.0])}
    state = adamw.init(params)
    for _ in range(150):
        grads = {"w": 2 * params["w"]}
        params, state, _ = adamw.update(grads, state, params, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.1


def test_adamw_grad_clip():
    cfg = AdamWConfig(grad_clip=1.0)
    g = {"w": jnp.full((4,), 100.0)}
    state = adamw.init(g)
    _, _, metrics = adamw.update(g, state, {"w": jnp.zeros((4,))}, cfg)
    assert float(metrics["grad_norm"]) == pytest.approx(200.0)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 1000))
def test_quantize_error_bound(seed):
    """int8 quantization error is bounded by scale/2 per element."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((32, 16)).astype(np.float32) * 10)
    q, scale = compression.quantize_int8(x)
    err = np.abs(np.asarray(compression.dequantize_int8(q, scale)) -
                 np.asarray(x))
    assert err.max() <= float(scale) * 0.5 + 1e-6


def test_error_feedback_preserves_signal():
    """Sum of EF-compressed gradients tracks the sum of true gradients —
    the residual never escapes (Karimireddy et al. property)."""
    rng = np.random.default_rng(0)
    grads = [{"w": jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)}
             for _ in range(20)]
    ef = compression.init_error_feedback(grads[0])
    total_hat = jnp.zeros((8, 8))
    total_true = jnp.zeros((8, 8))
    for g in grads:
        g_hat, ef = compression.compress_with_feedback(g, ef)
        total_hat += g_hat["w"]
        total_true += g["w"]
    resid = np.abs(np.asarray(total_hat + ef["w"] - total_true)).max()
    assert resid < 1e-4


@pytest.mark.slow
def test_compressed_training_converges():
    cfg = get_config("granite_3_2b").reduced()
    bundle = build(cfg, remat="none")
    opt = AdamWConfig(lr=1e-2, warmup_steps=2, total_steps=60,
                      weight_decay=0.0)
    state = init_train_state(bundle, jax.random.key(0), opt,
                             compress_grads=True)
    step = jax.jit(make_train_step(bundle, opt, compress_grads=True))
    data = SyntheticLM(cfg.vocab_size, 32, 4, seed=0)
    losses = []
    for i in range(25):
        state, m = step(state, data.batch_at(i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5  # learns through int8 compression


# ---------------------------------------------------------------- supervisor --

def _mk_trainer(tmp_path, n_ckpt=5):
    cfg = get_config("granite_3_2b").reduced()
    bundle = build(cfg, remat="none")
    opt = AdamWConfig(lr=5e-3, warmup_steps=2, total_steps=100,
                      weight_decay=0.0)
    state = init_train_state(bundle, jax.random.key(0), opt)
    step = jax.jit(make_train_step(bundle, opt))
    data = SyntheticLM(cfg.vocab_size, 32, 4, seed=0)
    ckpt = CheckpointManager(str(tmp_path), keep=3)
    return Trainer(bundle, opt, data, state, step, ckpt,
                   checkpoint_every=n_ckpt)


@pytest.mark.slow
def test_supervisor_restart_resumes_and_matches(tmp_path):
    """After an injected failure + restore, training must land on the SAME
    loss trajectory as an uninterrupted run (determinism of recovery)."""
    t_ref = _mk_trainer(tmp_path / "ref")
    ref_losses = [r.loss for r in t_ref.run(12)]

    t = _mk_trainer(tmp_path / "run")
    crashed = {}
    def bomb(step):
        if step == 8 and not crashed:
            crashed["x"] = True
            raise InjectedFailure()
    sup = Supervisor(t, failure_hook=bomb,
                     heartbeat_path=str(tmp_path / "hb.json"))
    rep = sup.run(12)
    assert rep.restarts == 1
    assert rep.completed_steps == 12
    # steps 10/11 (post-restore, re-run from ckpt@5) match the reference
    final = sorted(r.loss for r in t.records if r.step in (10, 11))
    ref = sorted(l for i, l in enumerate(ref_losses) if i in (10, 11))
    np.testing.assert_allclose(final, ref, rtol=1e-5)
    assert os.path.exists(tmp_path / "hb.json")


def test_supervisor_straggler_detection(tmp_path):
    t = _mk_trainer(tmp_path, n_ckpt=50)
    sup = Supervisor(t, straggler_factor=2.5,
                     delay_hook=lambda s: 0.3 if s == 9 else 0.0)
    rep = sup.run(12)
    assert 9 in rep.stragglers
    assert len(rep.stragglers) <= 3


@pytest.mark.slow
def test_supervisor_gives_up_after_max_restarts(tmp_path):
    t = _mk_trainer(tmp_path)
    def always_bomb(step):
        raise InjectedFailure()
    sup = Supervisor(t, max_restarts=2, failure_hook=always_bomb)
    with pytest.raises(InjectedFailure):
        sup.run(5)
    assert sup.restarts == 2


# -------------------------------------------------------------------- serve ----

def test_server_generates_consistent_with_forward():
    cfg = get_config("yi_6b").reduced()
    bundle = build(cfg, remat="none")
    params = bundle.init(jax.random.key(2))
    server = Server(bundle, params, max_len=32)
    prompts = np.asarray(
        bundle.make_batch(0, __import__("repro.configs.base",
                                        fromlist=["ShapeSpec"])
                          .ShapeSpec("p", 8, 2, "decode"),
                          train=False)["tokens"])

    # n_steps must be exact: generate(0) used to emit the prefill argmax
    # anyway, returning prompt+1 columns while reporting steps=0
    out0 = server.generate(prompts, n_steps=0)
    assert out0.tokens.shape == prompts.shape and out0.steps == 0
    np.testing.assert_array_equal(out0.tokens, prompts)
    out1 = server.generate(prompts, n_steps=1)
    assert out1.tokens.shape == (2, 9) and out1.steps == 1

    out = server.generate(prompts, n_steps=6)
    assert out.tokens.shape == (2, 14)
    # greedy decode must match greedy over the full forward logits
    full = bundle.forward(params, {"tokens": jnp.asarray(out.tokens[:, :-1])})
    greedy = np.asarray(jnp.argmax(full[:, 7:], axis=-1))
    np.testing.assert_array_equal(out.tokens[:, 8:], greedy)


def test_server_spans_split_a_batch_into_prefill_dispatch_and_fetch():
    from repro.core import tracing

    cfg = get_config("yi_6b").reduced()
    bundle = build(cfg, remat="none")
    server = Server(bundle, bundle.init(jax.random.key(3)), max_len=32)
    prompts = np.zeros((2, 8), np.int32)
    server.generate(prompts, n_steps=2)  # compiles
    with tracing.enabled() as rec:
        res = server.generate(prompts, n_steps=6)
    spans = rec.summary()["spans"]
    assert {n: v["count"] for n, v in spans.items()} == {
        "repro.serve.generate": 1, "repro.serve.prefill": 1,
        "repro.serve.decode_dispatch": 5, "repro.serve.token_fetch": 5}
    batch = {r.attrs["batch"] for r in rec.spans}
    assert batch == {server.batches}  # one batch id, shared by its children
    assert spans["repro.serve.prefill"]["total_s"] >= res.prefill_s
    steps = (spans["repro.serve.decode_dispatch"]["total_s"]
             + spans["repro.serve.token_fetch"]["total_s"])
    assert steps <= res.decode_s

    from repro.core import V5E, TuningDatabase
    from repro.runtime.serve_loop import decode_ops

    dispatching = Server(bundle, server.params, max_len=32, hw=V5E,
                         serve_ops=decode_ops(cfg, batch=2),
                         database=TuningDatabase())
    with tracing.enabled() as rec:
        dispatching.generate(prompts, n_steps=2)
    resolve, = [r for r in rec.spans
                if r.name == "repro.serve.resolve_dispatch"]
    assert resolve.parent.name == "repro.serve.generate"


def test_serve_launcher_smoke_checks_logits(monkeypatch, capsys):
    """The launcher serves the published config unless asked for the smoke
    one, prints a depth cut, and checks its logits against the f32
    reference."""
    from repro.launch import serve

    cfg = serve.serving_config("yi_6b", layers=8)
    published = get_config("yi_6b")
    assert cfg.n_layers == 8 and cfg.d_model == published.d_model
    assert serve.serving_config("yi_6b") == published
    monkeypatch.setattr("sys.argv", [
        "serve", "--smoke", "--batch", "2", "--prompt-len", "6",
        "--gen-steps", "4", "--check"])
    serve.main()
    out = capsys.readouterr().out
    assert "arch=yi-6b-smoke depth=2 layers" in out
    assert "logit error vs f32 reference" in out


def test_check_logits_catches_wrong_logits(monkeypatch):
    import dataclasses

    from repro.launch import serve

    # a bfloat16 server keeps its weights in bfloat16: the reference must
    # still run over the float32 masters, or it would not see their rounding
    cfg = dataclasses.replace(serve.serving_config("yi_6b", smoke=True),
                              dtype="bfloat16")
    server, prompts, _ = serve.build_server(cfg, 2, 6, 4)
    assert server.params["layers"]["attn"]["wq"].dtype == jnp.bfloat16
    res = server.generate(prompts, 4)

    ref_dtypes = set()

    def recording_build(ref_cfg, **kwargs):
        bundle = build(ref_cfg, **kwargs)
        forward = bundle.forward

        def recorded(params, batch):
            ref_dtypes.update(leaf.dtype for leaf in jax.tree.leaves(params))
            return forward(params, batch)

        bundle.forward = recorded
        return bundle

    monkeypatch.setattr(serve, "build", recording_build)
    assert max(serve.check_logits(server, res, 6)) <= serve.LOGIT_TOLERANCE
    assert ref_dtypes == {jnp.dtype(jnp.float32)}
    res.logits[1] = res.logits[1][:, ::-1]  # logits of the wrong tokens
    assert max(serve.check_logits(server, res, 6)) > serve.LOGIT_TOLERANCE


def _bf16_yi_smoke():
    import dataclasses

    return dataclasses.replace(get_config("yi_6b").reduced(),
                               dtype="bfloat16")


_CAST_LEAVES = {"embedding", "lm_head", "wq", "wk", "wv", "wo", "w_up",
                "w_gate", "w_down"}


def _leaf_name(path):
    return path[-1].key


def test_server_on_f32_masters_serves_what_the_model_does_on_them():
    """Weights cast once to bfloat16 when the server is built give the
    tokens and per-step logits the model gives on the float32 masters,
    which it casts at every use."""
    cfg = _bf16_yi_smoke()
    bundle = build(cfg, remat="none")
    params = bundle.init(jax.random.key(4))
    server = Server(bundle, params, max_len=32)
    prompts = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (2, 8)).astype(np.int32)
    res = server.generate(prompts, n_steps=6)

    prefill = jax.jit(lambda p, t: bundle.prefill_fn(p, {"tokens": t}, 32))
    decode = jax.jit(bundle.decode_fn)
    logits, cache = prefill(params, jnp.asarray(prompts))
    want_logits = [logits[:, -1]]
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    want_tokens = [tok]
    for i in range(5):
        logits, cache = decode(params, cache, tok[:, None], jnp.int32(8 + i))
        want_logits.append(logits)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        want_tokens.append(tok)

    np.testing.assert_array_equal(res.tokens[:, 8:], np.stack(want_tokens, 1))
    assert len(res.logits) == len(want_logits)
    for got, want in zip(res.logits, want_logits):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


def test_serving_params_casts_projections_and_keeps_norms_f32():
    cfg = _bf16_yi_smoke()
    bundle = build(cfg, remat="none")
    params = bundle.init(jax.random.key(0))
    served = bundle.serving_params(params)
    names = set()
    for (path, leaf), orig in zip(
            jax.tree_util.tree_flatten_with_path(served)[0],
            jax.tree.leaves(params)):
        name = _leaf_name(path)
        names.add(name)
        if name in _CAST_LEAVES:
            assert leaf.dtype == jnp.bfloat16, name
            np.testing.assert_array_equal(
                np.asarray(leaf, np.float32),
                np.asarray(orig.astype(jnp.bfloat16), np.float32))
        else:  # the norm scales, read in float32 by rms_norm
            assert name in {"ln1", "ln2", "final_norm"}, name
            assert leaf is orig and leaf.dtype == jnp.float32
    assert _CAST_LEAVES <= names
    # a tree already served passes through leaf for leaf
    again = bundle.serving_params(served)
    assert all(a is b for a, b in zip(jax.tree.leaves(again),
                                      jax.tree.leaves(served)))


@pytest.mark.parametrize("arch,dtype", [("yi_6b", "float32"),
                                        ("mamba2_780m", "bfloat16")])
def test_serving_params_is_identity_for_f32_and_other_families(arch, dtype):
    import dataclasses

    cfg = dataclasses.replace(get_config(arch).reduced(), dtype=dtype)
    bundle = build(cfg, remat="none")
    params = bundle.init(jax.random.key(0))
    assert bundle.serving_params(params) is params


def test_server_traces_prepare_params_and_cast_bytes():
    from repro.core import tracing

    cfg = _bf16_yi_smoke()
    bundle = build(cfg, remat="none")
    params = bundle.init(jax.random.key(5))
    with tracing.enabled() as rec:
        server = Server(bundle, params, max_len=32)
    summary = rec.summary()
    assert summary["spans"]["repro.serve.prepare_params"]["count"] == 1
    want = sum(leaf.size * 2 for path, leaf in
               jax.tree_util.tree_flatten_with_path(params)[0]
               if _leaf_name(path) in _CAST_LEAVES)
    assert want > 0
    assert summary["counters"]["repro.serve.params_cast_bytes"] == want
    assert want == sum(leaf.nbytes for leaf in jax.tree.leaves(server.params)
                       if leaf.dtype == jnp.bfloat16)


@pytest.mark.slow
def test_train_step_perf_knobs_numerics():
    """The §Perf train knobs (bf16 cast-once, explicit ZeRO-3 gather specs)
    must preserve training semantics."""
    from jax.sharding import PartitionSpec as P
    cfg = get_config("granite_3_2b").reduced()
    bundle = build(cfg, remat="none")
    opt = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10,
                      weight_decay=0.0)
    state = init_train_state(bundle, jax.random.key(0), opt)
    batch = SyntheticLM(cfg.vocab_size, 32, 4, seed=0).batch_at(0)

    base_step = jax.jit(make_train_step(bundle, opt))
    _, m0 = base_step(state, batch)

    specs = jax.tree.map(lambda _: P(), state["params"])
    knob_step = jax.jit(make_train_step(bundle, opt, cast_params_once=True,
                                        param_gather_specs=specs))
    from repro.launch.mesh import make_host_mesh
    with make_host_mesh():
        _, m1 = knob_step(state, batch)
    # bf16 cast perturbs the loss slightly; same order, finite, same scale
    assert np.isfinite(float(m1["loss"]))
    assert abs(float(m1["loss"]) - float(m0["loss"])) < 0.1
