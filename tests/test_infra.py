"""Data pipeline, checkpointing, sharding rules."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpoint import (
    load_checkpoint,
    save_checkpoint,
    save_sharded_checkpoint,
)
from repro.launch.topology import Topology
from repro.configs.base import (
    AttentionConfig,
    BlockSpec,
    MoEConfig,
    ModelConfig,
    OptimizerConfig,
)
from repro.data import SyntheticLM, batches
from repro.models import init_model
from repro.optim.factory import build_optimizer
from repro.sharding.rules import (
    generic_activation_pspec,
    opt_state_pspecs,
    param_pspec,
    params_pspecs,
    tokens_pspec,
)

MESH = {"data": 16, "model": 16}


def test_data_deterministic_and_learnable():
    cfg = ModelConfig(vocab_size=64)
    b1 = next(batches(cfg, 4, 32, seed=3))
    b2 = next(batches(cfg, 4, 32, seed=3))
    np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    assert b1["labels"].shape == (4, 32)
    # labels are next-token shifted
    stream = SyntheticLM(64, seed=0)
    toks = stream.sample(2, 16)
    assert toks.shape == (2, 17)
    # planted Markov structure: transition entropy < unigram entropy
    table = stream.table
    p = table.mean(axis=0)
    h_uni = -(p * np.log(p + 1e-12)).sum()
    h_cond = -(table * np.log(table + 1e-12)).sum(axis=1).mean()
    assert h_cond < h_uni - 0.1  # there is something to learn


def test_sampler_rounding_edge_clamps_to_last_token():
    """Regression: when float rounding leaves u >= cum[-1], the old
    `(u < cum).argmax` draw returned token 0 (argmax of all-False); the
    clamped searchsorted draw must land at the tail of the distribution."""
    stream = SyntheticLM(32, seed=0)

    class EdgeRng:
        """rand() returns 1.0 — beyond every row's cumsum — to force the edge."""

        def __init__(self, inner):
            self.inner = inner

        def rand(self, *shape):
            return np.ones(shape)

        def randint(self, *a, **k):
            return self.inner.randint(*a, **k)

    stream.rng = EdgeRng(stream.rng)
    toks = stream.sample(4, 8)
    assert (toks >= 0).all() and (toks < 32).all()
    # every draw hit the u >= cum[-1] edge: must clamp to the tail, never
    # fall back to token 0 (the most-probable Zipf head — a silent bias)
    assert (toks[:, 1:] != 0).all()
    assert (toks[:, 1:] >= 30).all()


def test_sampler_off_edge_draw_unchanged():
    """The searchsorted draw is the first index with cum > u — identical to
    the previous strict-inequality argmax away from the rounding edge, so
    fixed-seed token streams are preserved."""
    stream = SyntheticLM(64, seed=5)
    toks = stream.sample(8, 32)
    ref = SyntheticLM(64, seed=5)
    out = np.empty_like(toks)
    out[:, 0] = ref.rng.randint(0, 64, size=8)
    for t in range(32):
        cum = np.cumsum(ref._rows(out[:, t]), axis=1)
        u = ref.rng.rand(8, 1)
        old = (u < cum).argmax(axis=1)  # the pre-fix formula
        valid = (u < cum[:, -1:]).ravel()  # rows where it was well-defined
        new_draw = np.minimum((cum <= u).sum(axis=1), 63)
        np.testing.assert_array_equal(new_draw[valid], old[valid])
        out[:, t + 1] = new_draw
    np.testing.assert_array_equal(toks, out)


def test_sampler_golden_stream_and_table_dtype():
    """Regression for the f64 leak fix: the exposed transition `table` is now
    float32 (no f64 arrays cross into jit'd code), but the SAMPLING path
    still draws through the implicit-f64 numpy pipeline, so fixed-seed token
    streams are bit-identical to the pre-fix values captured below."""
    stream = SyntheticLM(64, seed=5)
    assert stream.table.dtype == np.float32
    golden = np.array([
        [10, 0, 9, 47, 39, 51, 62, 44, 55, 18, 46, 46, 46],
        [9, 60, 0, 9, 6, 22, 28, 25, 60, 10, 0, 37, 23],
    ])
    np.testing.assert_array_equal(stream.sample(2, 12), golden)


def test_sharded_batches_partition_global_stream():
    """Regression: host shards must be slices of the SAME seeded global
    stream — concatenating them reproduces `batches(...)` bit-for-bit at
    every step (the old ``seed * num_hosts + host_id`` scheme gave hosts
    unrelated streams that partitioned nothing)."""
    from repro.data import host_assembled_batches, sharded_batches

    cfg = ModelConfig(vocab_size=64)
    ref = batches(cfg, 8, 16, seed=3)
    its = [sharded_batches(cfg, 8, 16, 4, h, seed=3) for h in range(4)]
    asm = host_assembled_batches(cfg, 8, 16, 4, seed=3)
    for _ in range(3):
        want = next(ref)
        shards = [next(it) for it in its]
        for key in ("tokens", "labels"):
            assert all(s[key].shape == (2, 16) for s in shards)
            cat = np.concatenate([np.asarray(s[key]) for s in shards], axis=0)
            np.testing.assert_array_equal(cat, np.asarray(want[key]))
        got = next(asm)
        for key in ("tokens", "labels"):
            np.testing.assert_array_equal(
                np.asarray(got[key]), np.asarray(want[key])
            )


def test_sharded_batches_single_host_stream_unchanged():
    """num_hosts=1 must reproduce the historical `batches(seed)` stream, so
    existing fixed-seed runs are untouched by the partition fix."""
    from repro.data import sharded_batches

    cfg = ModelConfig(vocab_size=64)
    a = sharded_batches(cfg, 8, 16, 1, 0, seed=5)
    b = batches(cfg, 8, 16, seed=5)
    for _ in range(2):
        x, y = next(a), next(b)
        np.testing.assert_array_equal(np.asarray(x["tokens"]), np.asarray(y["tokens"]))
        np.testing.assert_array_equal(np.asarray(x["labels"]), np.asarray(y["labels"]))


@pytest.mark.parametrize("num_hosts", [1, 2, 4, 8])
def test_sharded_batches_any_divisor_split_partitions_stream(num_hosts):
    """Property (deterministic sweep; the hypothesis version lives in
    test_data_properties.py): for EVERY host count dividing the batch,
    (a) per-host slices concatenate bit-for-bit to the global `batches()`
    stream at every step, and (b) a fresh host iterator fast-forwarded k
    steps — the resume path — matches the uninterrupted host stream."""
    from repro.data import sharded_batches

    cfg = ModelConfig(vocab_size=64)
    B, S, steps = 8, 16, 3
    ref = batches(cfg, B, S, seed=11)
    its = [sharded_batches(cfg, B, S, num_hosts, h, seed=11)
           for h in range(num_hosts)]
    stream = [[next(it) for it in its] for _ in range(steps)]
    for t in range(steps):
        want = next(ref)
        for key in want:
            cat = np.concatenate(
                [np.asarray(s[key]) for s in stream[t]], axis=0
            )
            np.testing.assert_array_equal(cat, np.asarray(want[key]))
    for h in (0, num_hosts - 1):
        for k in (0, steps - 1):
            fresh = sharded_batches(cfg, B, S, num_hosts, h, seed=11)
            for _ in range(k):
                next(fresh)
            got = next(fresh)
            for key in got:
                np.testing.assert_array_equal(
                    np.asarray(got[key]), np.asarray(stream[k][h][key])
                )


@pytest.mark.parametrize("data_shards,splits", [
    (1, [(0, 1)]),
    (2, [(0, 1), (1, 2)]),
    (2, [(0, 2)]),
    (4, [(0, 2), (2, 4)]),
    (4, [(0, 1), (1, 2), (2, 3), (3, 4)]),
])
def test_process_local_batches_partition_microbatched_stream(
    data_shards, splits
):
    """The multi-controller loader must reproduce the global MICROBATCHED
    array: stacking each process's `[lo, hi)` row-shard slice along the
    shard axis equals ``batches()`` reshaped (M, shards, w, S) — the
    invariant that makes loss curves independent of process count and lets
    elastic resumes continue the identical stream."""
    from repro.data import process_local_batches

    cfg = ModelConfig(vocab_size=64)
    B, S, M = 8, 16, 2
    w = B // M // data_shards
    ref = batches(cfg, B, S, seed=4)
    its = [
        process_local_batches(cfg, B, S, num_microbatches=M,
                              data_shards=data_shards, shard_lo=lo,
                              shard_hi=hi, seed=4)
        for lo, hi in splits
    ]
    for _ in range(3):
        want = next(ref)
        parts = [next(it) for it in its]
        for key in want:
            glob = np.asarray(want[key]).reshape(M, data_shards, w, -1)
            got = np.concatenate(
                [np.asarray(p[key]).reshape(M, hi - lo, w, -1)
                 for p, (lo, hi) in zip(parts, splits)],
                axis=1,
            )
            np.testing.assert_array_equal(got, glob)


def test_data_modalities():
    audio = ModelConfig(vocab_size=32, num_codebooks=4)
    b = next(batches(audio, 2, 8))
    assert b["tokens"].shape == (2, 8, 4)
    vlm = ModelConfig(vocab_size=32, frontend="vision", frontend_tokens=3, frontend_dim=16)
    b = next(batches(vlm, 2, 8))
    assert b["frontend"].shape == (2, 3, 16)


def test_checkpoint_roundtrip(tmp_path):
    cfg = ModelConfig(
        num_layers=2, d_model=32, d_ff=64, vocab_size=64,
        attention=AttentionConfig(num_heads=2, num_kv_heads=2, head_dim=16),
        pattern=(BlockSpec("attn", "dense"),), scan_layers=False,
    )
    params = init_model(jax.random.PRNGKey(0), cfg)
    opt = build_optimizer(
        OptimizerConfig(name="basis_rotation", total_steps=10), params, cfg, num_stages=2
    )
    state = opt.init(params)
    g = jax.tree.map(jnp.ones_like, params)
    _, state = opt.update(g, state, params, jnp.int32(0))
    path = str(tmp_path / "ckpt")
    save_checkpoint(path, (params, state), step=7, meta={"note": "t"})
    (p2, s2), step, meta = load_checkpoint(path)
    assert step == 7 and meta["note"] == "t"
    assert jax.tree.structure((params, state)) == jax.tree.structure((p2, s2))
    for a, b in zip(jax.tree.leaves((params, state)), jax.tree.leaves((p2, s2))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype


def test_checkpoint_interrupted_save_keeps_previous(tmp_path, monkeypatch):
    """A crash mid-save must not corrupt the only checkpoint: files are
    written to temp names and atomically swapped in (arrays first, manifest
    last), so the pre-crash checkpoint stays loadable."""
    path = str(tmp_path / "ckpt")
    tree1 = {"w": jnp.arange(4.0), "b": jnp.ones((2, 2))}
    save_checkpoint(path, tree1, step=1, meta={"note": "good"})

    # crash while writing the arrays file (partial bytes on disk, then die)
    def savez_boom(file, **kw):
        with open(file, "wb") as f:
            f.write(b"\x00partial-garbage")
        raise RuntimeError("simulated crash during array write")

    monkeypatch.setattr(np, "savez", savez_boom)
    with pytest.raises(RuntimeError, match="simulated crash"):
        save_checkpoint(path, {"w": jnp.arange(4.0) + 9, "b": jnp.zeros((2, 2))},
                        step=2)
    monkeypatch.undo()

    tree, step, meta = load_checkpoint(path)
    assert step == 1 and meta["note"] == "good"
    np.testing.assert_array_equal(np.asarray(tree["w"]), np.arange(4.0))

    # crash while serialising the manifest: same guarantee
    import json as _json

    real_dump = _json.dump

    def dump_boom(obj, f, **kw):
        f.write('{"spec": "trunc')
        raise RuntimeError("simulated crash during manifest write")

    monkeypatch.setattr(_json, "dump", dump_boom)
    with pytest.raises(RuntimeError, match="simulated crash"):
        save_checkpoint(path, tree1, step=3)
    monkeypatch.setattr(_json, "dump", real_dump)

    tree, step, _ = load_checkpoint(path)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(tree["b"]), np.ones((2, 2)))

    # crash after the arrays file commits but before the manifest swap: the
    # old manifest still names the old arrays file — never a mixed state
    real_replace = os.replace

    def replace_boom(src, dst):
        if dst.endswith("manifest.json"):
            raise RuntimeError("simulated crash before manifest commit")
        return real_replace(src, dst)

    monkeypatch.setattr(os, "replace", replace_boom)
    with pytest.raises(RuntimeError, match="simulated crash"):
        save_checkpoint(path, {"w": jnp.arange(4.0) + 9, "b": jnp.zeros((2, 2))},
                        step=4)
    monkeypatch.setattr(os, "replace", real_replace)

    tree, step, _ = load_checkpoint(path)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(tree["w"]), np.arange(4.0))


def test_topology_shapes_and_axes():
    t = Topology.single_host(4)
    assert t.shape == (4, 1) and t.axis_names == ("stage", "data")
    assert t.schedule_data_axis == "data" and t.data_shards == 1
    p = Topology.single_pod()
    assert p.shape == (16, 16) and p.num_devices == 256
    m = Topology.multi_pod()
    assert m.shape == (2, 16, 16) and m.axis_names == ("pod", "stage", "data")
    assert m.schedule_data_axis == ("pod", "data")
    assert m.data_shards == 32 and m.num_devices == 512
    assert m.describe() == "2x16x16"
    assert m.stage_spec(3) == P("stage", None, None)
    assert m.batch_spec() == P(None, ("pod", "data"), None)
    assert Topology.from_device_count(4, pods=2, data=0, device_count=16) == \
        Topology(stages=4, data=2, pods=2)
    with pytest.raises(ValueError):
        Topology.from_device_count(3, device_count=16)
    with pytest.raises(ValueError):
        Topology(stages=0)


def test_topology_mesh_roundtrip():
    # single device: the smoke (stage=1, data=1) mesh carries the axis names
    t = Topology.single_host(1)
    mesh = t.make_mesh()
    assert Topology.from_mesh(mesh) == t


def test_sharded_checkpoint_roundtrip_equals_gathered(tmp_path):
    """One arrays file per stage shard must reassemble to exactly the tree a
    gathered save stores — values, dtypes and structure."""
    tree = (
        {"stacked": jnp.arange(24.0).reshape(4, 3, 2),
         "fifo": jnp.arange(24.0).reshape(3, 4, 2) * 2.0},
        {"shared": jnp.ones((5,), jnp.float32), "count": jnp.int32(7)},
    )
    # tree_flatten order: fifo, stacked, count, shared
    axes = [1, 0, None, None]
    sharded = str(tmp_path / "sharded")
    gathered = str(tmp_path / "gathered")
    save_sharded_checkpoint(sharded, tree, num_shards=4, step=9,
                            shard_axes=axes, meta={"topology": "4x1"})
    save_checkpoint(gathered, tree, step=9)
    names = sorted(os.listdir(sharded))
    assert sum(n.endswith(".npz") for n in names) == 4  # one file per shard
    a, step_a, meta_a = load_checkpoint(sharded)
    b, step_b, _ = load_checkpoint(gathered)
    assert step_a == step_b == 9 and meta_a["topology"] == "4x1"
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert np.asarray(x).dtype == np.asarray(y).dtype


def test_sharded_checkpoint_rejects_bad_axis(tmp_path):
    tree = {"w": jnp.zeros((3, 2))}
    with pytest.raises(ValueError, match="not divisible"):
        save_sharded_checkpoint(str(tmp_path), tree, num_shards=2,
                                shard_axes=[0])


def test_sharded_checkpoint_same_step_resave_never_overwrites(tmp_path, monkeypatch):
    """Re-saving the SAME step (re-run into an old dir, the loop's final-step
    double save) must not replace committed shard files in place: a crash
    mid-save would otherwise leave the old manifest naming a mixed
    old/new shard set. Fresh generation-suffixed names keep the previous
    checkpoint fully consistent until the new manifest commits."""
    path = str(tmp_path / "ckpt")
    axes = [0]
    save_sharded_checkpoint(path, {"w": jnp.zeros((2, 2))}, num_shards=2,
                            step=5, shard_axes=axes, meta={"run": "old"})
    old_files = {n for n in os.listdir(path) if n.endswith(".npz")}

    # crash after the first shard file of the re-save is committed
    real_savez = np.savez
    calls = {"n": 0}

    def savez_boom(file, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("simulated crash during shard write")
        return real_savez(file, **kw)

    monkeypatch.setattr(np, "savez", savez_boom)
    with pytest.raises(RuntimeError, match="simulated crash"):
        save_sharded_checkpoint(path, {"w": jnp.ones((2, 2))}, num_shards=2,
                                step=5, shard_axes=axes, meta={"run": "new"})
    monkeypatch.undo()

    # every old shard file is untouched and the old tree loads exactly
    assert old_files <= {n for n in os.listdir(path)}
    tree, step, meta = load_checkpoint(path)
    assert step == 5 and meta["run"] == "old"
    np.testing.assert_array_equal(np.asarray(tree["w"]), np.zeros((2, 2)))

    # a successful re-save commits under fresh names and GCs the old set
    save_sharded_checkpoint(path, {"w": jnp.ones((2, 2))}, num_shards=2,
                            step=5, shard_axes=axes, meta={"run": "new"})
    tree, _, meta = load_checkpoint(path)
    assert meta["run"] == "new"
    np.testing.assert_array_equal(np.asarray(tree["w"]), np.ones((2, 2)))
    assert not (old_files & {n for n in os.listdir(path)})


def test_sharded_checkpoint_interrupted_save_keeps_previous(tmp_path, monkeypatch):
    """The manifest swap is the single commit point for the whole shard file
    set: a crash while writing any shard file — or before the manifest
    lands — must leave the previous sharded checkpoint loadable."""
    path = str(tmp_path / "ckpt")
    tree1 = {"w": jnp.arange(8.0).reshape(4, 2), "b": jnp.ones((3,))}
    axes = [None, 0]  # flatten order: b, w
    save_sharded_checkpoint(path, tree1, num_shards=4, step=1, shard_axes=axes,
                            meta={"note": "good"})

    # crash while writing the third shard file
    real_savez = np.savez
    calls = {"n": 0}

    def savez_boom(file, **kw):
        calls["n"] += 1
        if calls["n"] == 3:
            with open(file, "wb") as f:
                f.write(b"\x00partial-garbage")
            raise RuntimeError("simulated crash during shard write")
        return real_savez(file, **kw)

    monkeypatch.setattr(np, "savez", savez_boom)
    with pytest.raises(RuntimeError, match="simulated crash"):
        save_sharded_checkpoint(path, {"w": jnp.zeros((4, 2)), "b": jnp.zeros((3,))},
                                num_shards=4, step=2, shard_axes=axes)
    monkeypatch.undo()

    tree, step, meta = load_checkpoint(path)
    assert step == 1 and meta["note"] == "good"
    np.testing.assert_array_equal(np.asarray(tree["w"]),
                                  np.arange(8.0).reshape(4, 2))

    # crash before the manifest commit: all new shard files on disk, but the
    # old manifest still names the old (complete) set
    real_replace = os.replace

    def replace_boom(src, dst):
        if dst.endswith("manifest.json"):
            raise RuntimeError("simulated crash before manifest commit")
        return real_replace(src, dst)

    monkeypatch.setattr(os, "replace", replace_boom)
    with pytest.raises(RuntimeError, match="simulated crash"):
        save_sharded_checkpoint(path, {"w": jnp.zeros((4, 2)), "b": jnp.zeros((3,))},
                                num_shards=4, step=3, shard_axes=axes)
    monkeypatch.setattr(os, "replace", real_replace)

    tree, step, _ = load_checkpoint(path)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(tree["b"]), np.ones((3,)))

    # a successful save GCs the stranded step-2/3 shard files
    save_sharded_checkpoint(path, tree1, num_shards=4, step=4, shard_axes=axes)
    left = sorted(n for n in os.listdir(path) if n.endswith(".npz"))
    assert left == [f"arrays-00000004-shard{s:05d}-of-00004.npz" for s in range(4)]


def test_param_pspec_rules():
    assert param_pspec("embed/embedding", (64000, 7168), MESH) == P("model", "data")
    assert param_pspec("lm_head", (7168, 64000), MESH) == P("data", "model")
    assert param_pspec("blocks/0/mixer/w_q", (60, 7168, 7168), MESH) == P(None, "data", "model")
    assert param_pspec("blocks/0/mixer/w_o", (7168, 7168), MESH) == P("model", "data")
    # expert parallel when experts divide the axis
    assert param_pspec("blocks/0/mlp/w_gate_e", (160, 5120, 1536), MESH) == P("model", "data", None)
    # hidden-dim fallback when they don't (mixtral: 8 experts, 16-way axis)
    assert param_pspec("blocks/0/mlp/w_gate_e", (8, 6144, 16384), MESH) == P(None, "data", "model")
    assert param_pspec("blocks/0/mlp/w_down_e", (8, 16384, 6144), MESH) == P(None, "model", "data")
    # non-divisible dims degrade to None, norms replicated
    assert param_pspec("blocks/0/mixer/w_q", (100, 50), MESH) == P(None, None)
    assert param_pspec("blocks/0/norm1/scale", (7168,), MESH) == P(None)


def test_opt_state_pspecs_structure():
    cfg = ModelConfig(
        num_layers=2, d_model=64, d_ff=128, vocab_size=256,
        attention=AttentionConfig(num_heads=4, num_kv_heads=4, head_dim=16),
        pattern=(BlockSpec("attn", "dense"),),
    )
    params = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
    opt = build_optimizer(
        OptimizerConfig(name="basis_rotation", total_steps=10), params, cfg,
        num_stages=1, apply_delay=False,
    )
    st = jax.eval_shape(opt.init, params)
    specs = opt_state_pspecs(st, params, MESH)
    # every state leaf got a spec of matching rank
    flat_s = jax.tree.leaves(st)
    flat_p = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_s) == len(flat_p)
    for aval, spec in zip(flat_s, flat_p):
        assert isinstance(spec, P)
        assert len(spec) <= len(aval.shape)


def test_token_and_activation_specs():
    assert tokens_pspec(256, MESH) == P(("data",), None)
    assert tokens_pspec(7, MESH) == P(None, None)  # indivisible
    ms3 = {"pod": 2, "data": 16, "model": 16}
    assert tokens_pspec(256, ms3) == P(("pod", "data"), None)
    spec = generic_activation_pspec((128, 8, 32768, 128), MESH, batch_dim=0)
    assert spec[0] in ("data", ("data",)) and "model" in spec


def test_compile_cache_follows_env_and_skips_cpu(monkeypatch):
    """A set JAX_COMPILATION_CACHE_DIR is left to JAX; otherwise the cache
    is the checkout's fixed `.jax_cache`, and XLA:CPU stays uncached."""
    from repro.launch import compile_cache as cc

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cc.ENV_VAR, "/elsewhere")
    assert cc.use_compile_cache() == "/elsewhere"
    monkeypatch.delenv(cc.ENV_VAR)
    assert jax.default_backend() == "cpu"
    assert cc.use_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    assert cc.REPO_CACHE_DIR == cc.REPO_ROOT / ".jax_cache"
    assert (cc.REPO_ROOT / "pyproject.toml").is_file()
