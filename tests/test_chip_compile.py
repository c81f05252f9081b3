"""Ask the TPU's own compiler, from a machine with no TPU.

libtpu is installed next to the CPU-only jaxlib the tests run on, and it
compiles for a chip that is *described*, not attached
(``jax.experimental.topologies``).  Interpret mode — what every other
Pallas test here runs — lowers a kernel to ordinary HLO and so cannot see
what Mosaic refuses: a misaligned slice, too much VMEM, a kernel GSPMD is
asked to partition.  These cases compile the MAIN-PATH kernels at real
widths for a described v5e and keep that guard in tier-1 at no chip time.

Rules of this file (the driver runs the suite under ``xdist -n 6``; only
ONE process may load libtpu, and it keeps it until it exits):

- the topology is described inside the module-scoped, non-autouse ``topo``
  fixture, which skips from inside if it cannot — never at import, never in
  a ``skipif``/``parametrize`` argument, never in ``conftest.py``;
- shardings, meshes and shapes are built in fixtures or tests;
- every compile happens in the test's own process (no children);
- this is the only file of its kind: a second one could land on another
  worker, whose fixture would then skip every test in silence.

Nothing here runs on a device: a compile that passes is not a chip run.
"""

import functools
import os

import pytest


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def dp_mesh(topo):
    """The ``(data=4, spatial=1)`` mesh ``make_mesh`` would build on a
    four-chip host."""
    from raft_tpu.parallel.mesh import make_mesh

    return make_mesh(num_data=4, num_spatial=1, devices=topo.devices)


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the tests silent and the
    cache clean."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _with_sharding(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _lookup_operands(batch, h8, w8, sharding, channels=256):
    """Abstract ``(fmap1, fmap2, pyramid, coords)`` at 1/8-res ``h8 x w8``
    — the bf16-stored query-minor pyramid RAFT-full builds under bf16
    compute, by the model's own builder."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.ops.corr import build_corr_pyramid_flat

    fmap = jax.ShapeDtypeStruct((batch, h8, w8, channels), jnp.float32)
    pyramid = jax.eval_shape(
        functools.partial(build_corr_pyramid_flat, num_levels=4,
                          pad_q=128, out_dtype=jnp.bfloat16), fmap, fmap)
    coords = jax.ShapeDtypeStruct((batch, h8, w8, 2), jnp.float32)
    return _with_sharding((fmap, fmap, pyramid, coords), sharding)


# What the train step runs at the chairs crop (368x496 -> 46x62: the
# `bwd` case compiles the unrolled forward a differentiated call keeps
# and both transpose calls), what validate and both serve cells run at the
# Sintel shape (440x1024 -> 55x128; RAFT-small samples radius 3, which no
# train cell compiles) -- the `fwd` cases are undifferentiated calls and
# so the rolled-up forward --, a 1088x1920 map (136x240), and the
# on-demand kernel `evaluate --alternate_corr` picks on TPU.
@pytest.mark.parametrize("case", [
    "pyramid_lookup_fwd_46x62", "pyramid_lookup_bwd_46x62",
    "pyramid_lookup_fwd_55x128", "pyramid_lookup_fwd_55x128_r3",
    "pyramid_lookup_fwd_136x240", "ondemand_corr_fwd_55x128"])
def test_main_path_kernel_compiles_for_v5e(case, one_chip):
    import jax
    import jax.numpy as jnp

    from raft_tpu.ops.corr import pool_fmap_pyramid
    from raft_tpu.ops.pallas_corr import (pallas_corr_lookup,
                                          pallas_pyramid_lookup)

    h8, w8 = next(hw for tag, hw in (("46x62", (46, 62)),
                                     ("55x128", (55, 128)),
                                     ("136x240", (136, 240))) if tag in case)
    radius = 3 if case.endswith("_r3") else 4
    fmap1, fmap2, pyramid, coords = _lookup_operands(
        1 if h8 > 100 else 2, h8, w8, one_chip)

    def pyramid_lookup(pyr, c):
        # interpret=False explicitly: jax.default_backend() is "cpu"
        # here and would pick the interpreter.
        return pallas_pyramid_lookup(pyr, c, radius, 128, False,
                                     jnp.bfloat16)

    if case.startswith("ondemand"):
        def fn(f1, f2, c):
            return pallas_corr_lookup(
                f1, tuple(pool_fmap_pyramid(f2, 4)), c, 4, 128, False)

        args = (fmap1, fmap2, coords)
    elif "bwd" in case:
        def fn(pyr, c):
            return jax.grad(lambda p: jnp.sum(
                pyramid_lookup(p, c).astype(jnp.float32)))(pyr)

        args = (pyramid, coords)
    else:
        fn, args = pyramid_lookup, (pyramid, coords)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# The selection's VMEM estimate against Mosaic's own accounting, on both
# sides of the budget, at a 1088x1920 map (2.1 GB of bf16 pyramid: HBM is
# not what refuses).  Block 384 -> 71.5 MiB estimated, inside the 78 MiB
# budget: handed to the kernel, and it compiles.  Block 512 -> 95.4 MiB
# and block 640 -> 119 MiB: refused by the selection, and by Mosaic under
# the repo-wide 100 MiB ``vmem_limit_bytes``.
@pytest.mark.parametrize("block_q,path,compiles", [
    (384, "mosaic", True), (512, "xla", False), (640, "xla", False)])
def test_lookup_vmem_budget_brackets_what_mosaic_takes(block_q, path,
                                                       compiles, one_chip):
    import jax
    import jax.numpy as jnp

    from raft_tpu.ops.corr import build_corr_pyramid_flat
    from raft_tpu.ops.pallas_corr import (pallas_pyramid_lookup,
                                          pyramid_lookup_path)

    h8, w8 = 136, 240
    assert pyramid_lookup_path("tpu", h8, w8, levels=4, radius=4,
                               block_q=block_q, storage_bytes=2) == path
    fmap = jax.ShapeDtypeStruct((1, h8, w8, 8), jnp.float32)
    pyramid = jax.eval_shape(
        functools.partial(build_corr_pyramid_flat, num_levels=4,
                          pad_q=block_q, out_dtype=jnp.bfloat16),
        fmap, fmap)
    coords = jax.ShapeDtypeStruct((1, h8, w8, 2), jnp.float32)
    lowered = jax.jit(
        lambda pyr, c: pallas_pyramid_lookup(pyr, c, 4, block_q, False,
                                             jnp.bfloat16)
    ).lower(*_with_sharding((pyramid, coords), one_chip))
    if compiles:
        assert "tpu_custom_call" in lowered.compile().as_text()
    else:
        with pytest.raises(Exception, match="memory space vmem"):
            lowered.compile()


# GMFlow's window attention (``ops/pallas_attention.py``) as the model
# calls it, forward and backward, plain and shifted, at the train cell's
# shape (384x512 -> 48x64, 32 maps: windows of 24x32 = 768 tokens, a whole
# window a grid step) and at Sintel's (448x1024 -> 56x128, windows of
# 28x64 = 1,792 tokens).  The selection's VMEM estimate is held against
# Mosaic's own accounting from both sides: under a limit of the estimate the
# kernels compile, under half of it the backward is refused.
@pytest.mark.parametrize("maps,h8,w8,dtype", [
    (32, 48, 64, "bfloat16"), (2, 56, 128, "bfloat16"),
    (2, 48, 48, "float32")])      # 24x24 windows: float32's tile alone
def test_window_attention_compiles_inside_its_vmem_estimate(
        maps, h8, w8, dtype, one_chip, monkeypatch):
    import math

    import jax
    import jax.numpy as jnp

    from raft_tpu.models import gmflow
    from raft_tpu.ops import pallas_attention
    from raft_tpu.ops.pallas_util import tpu_pallas_call

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dtype = jnp.dtype(dtype)
    assert gmflow.window_attention_path(h8, w8, 128, dtype) == "mosaic"
    x = jax.ShapeDtypeStruct((maps, h8 * w8, 128), dtype, sharding=one_chip)

    def compile_under(limit_mb):
        monkeypatch.setattr(
            pallas_attention, "tpu_pallas_call",
            functools.partial(tpu_pallas_call, vmem_limit_mb=limit_mb))

        def both(q, k, v, g):      # a new function: nothing traced is reused
            out = []
            for shift in (False, True):
                o, vjp = jax.vjp(lambda q, k, v: gmflow.window_attention(
                    q, k, v, h8, w8, shift, dtype), q, k, v)
                out.append((o, vjp(g)))
            return out

        return jax.jit(both).lower(x, x, x, x).compile().as_text()

    hk, wk = h8 // gmflow.SPLITS, w8 // gmflow.SPLITS
    rows = pallas_attention.window_block_rows(hk, wk, 128, dtype.itemsize)
    estimate = math.ceil(pallas_attention.window_attention_vmem_bytes(
        rows, hk, wk, 128, dtype.itemsize) / 2 ** 20)
    text = compile_under(estimate)
    assert text.count("tpu_custom_call") == 4
    # the windows are addressed by the kernels' block specs: no array of
    # windows (``[128,768,128]``, ``[128,768,768]``) is in the program
    assert f"[{4 * maps},{hk * wk}," not in text
    with pytest.raises(Exception, match="memory space vmem"):
        compile_under(estimate // 2)


def test_window_attention_compiles_at_the_quarter_scale_windows(
        one_chip, monkeypatch):
    """Arch 'unimatch''s second scale: the 1/4 map of a 384x512 crop
    (96x128, both images of 8 pairs) in 8x8 windows of 12x16 = 192
    tokens, a whole window a grid step, forward and backward, plain and
    shifted, by the same kernels as the 1/8 map's 2x2 windows."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.models import gmflow
    from raft_tpu.ops import pallas_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dtype = jnp.dtype(jnp.bfloat16)
    assert gmflow.window_attention_path(96, 128, 128, dtype, 8) == "mosaic"
    assert pallas_attention.window_block_rows(12, 16, 128, 2) == 12
    x = jax.ShapeDtypeStruct((16, 96 * 128, 128), dtype, sharding=one_chip)

    def both(q, k, v, g):
        out = []
        for shift in (False, True):
            o, vjp = jax.vjp(lambda q, k, v: gmflow.window_attention(
                q, k, v, 96, 128, shift, dtype, splits=8), q, k, v)
            out.append((o, vjp(g)))
        return out

    text = jax.jit(both).lower(x, x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") == 4
    assert "[1024,192," not in text     # no array of windows


def test_one_level_lookup_compiles_at_the_quarter_map(one_chip):
    """Arch 'unimatch''s refinement: the lookup of radius 4 into a
    one-level volume at 96x128 (the 1/4 map of a 384x512 crop), its
    forward and its backward, as a differentiated call keeps them."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.ops.corr import build_corr_pyramid_flat
    from raft_tpu.ops.pallas_corr import (pallas_pyramid_lookup,
                                          pyramid_lookup_path)

    assert pyramid_lookup_path("tpu", 96, 128, levels=1, radius=4,
                               block_q=128, storage_bytes=2) == "mosaic"
    fmap = jax.ShapeDtypeStruct((2, 96, 128, 128), jnp.float32)
    pyramid = jax.eval_shape(
        functools.partial(build_corr_pyramid_flat, num_levels=1,
                          pad_q=128, out_dtype=jnp.bfloat16), fmap, fmap)
    coords = jax.ShapeDtypeStruct((2, 96, 128, 2), jnp.float32)
    pyramid, coords = _with_sharding((pyramid, coords), one_chip)

    def fn(pyr, c):
        return jax.value_and_grad(lambda p: jnp.sum(pallas_pyramid_lookup(
            p, c, 4, 128, False, jnp.bfloat16).astype(jnp.float32)))(pyr)

    text = jax.jit(fn).lower(pyramid, coords).compile().as_text()
    assert text.count("tpu_custom_call") >= 2


def test_differentiated_quarter_map_lookup_writes_no_volume_cotangent(
        one_chip, monkeypatch):
    """Arch 'unimatch''s refinement, differentiated as a train step does
    it: the model's own corr state and lookup at the 1/4 map of a
    384x512 crop (batch 2), two lookups, gradients of both maps.  The
    window kernels return the maps' gradients, so no array of the dense
    cotangent's shape ``bf16[2,96,128,12288]`` is written by a kernel or
    summed: the volume's build is the only thing of that shape."""
    import re

    import jax
    import jax.numpy as jnp

    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import (WindowCorrState, _build_corr_state,
                                      lookup_corr)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = RAFTConfig.preset("unimatch", compute_dtype="bfloat16")
    fmap = jax.ShapeDtypeStruct((2, 96, 128, 128), jnp.bfloat16,
                                sharding=one_chip)
    coords = jax.ShapeDtypeStruct((2, 96, 128, 2), jnp.float32,
                                  sharding=one_chip)

    def loss(f1, f2, c):
        state = _build_corr_state(cfg, f1, f2, True)
        assert isinstance(state, WindowCorrState)
        return sum(jnp.sum(jnp.square(lookup_corr(
            cfg, state, c + i, jnp.bfloat16).astype(jnp.float32)))
            for i in range(2))

    # an inference program has no backward to choose: the pyramid alone
    assert not isinstance(jax.eval_shape(
        lambda a, b: _build_corr_state(cfg, a, b), fmap, fmap),
        WindowCorrState)
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        fmap, fmap, coords).compile().as_text()
    made = re.findall(r"= bf16\[2,96,128,12288\]\{[^}]*\} ([\w-]+)\(",
                      text)
    assert made and len(made) <= 2, made
    assert "custom-call" not in made and "add" not in made, made
    # two forward lookups, two window backwards (df1, df2 in float32)
    assert len(re.findall(r"= bf16\[2,81,12288\]\{[^}]*\} custom-call",
                          text)) == 2
    assert len(re.findall(r"= \(f32\[2,12288,128\]\{[^}]*\}, "
                          r"f32\[2,96,128,128\]\{[^}]*\}\) custom-call",
                          text)) == 2


def test_window_attention_partitions_over_data_mesh(dp_mesh, monkeypatch):
    """With the maps sharded over ``data`` the window attention lowers per
    shard (``per_data_shard``), forward and backward, shifted: what a
    four-chip ``--arch gmflow`` step holds."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from raft_tpu.models import gmflow
    from raft_tpu.parallel.mesh import DATA_AXIS, data_parallel_kernels

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = jax.ShapeDtypeStruct((32, 48 * 64, 128), jnp.bfloat16,
                             sharding=NamedSharding(dp_mesh, P(DATA_AXIS)))

    def step(q, k, v, g):
        with data_parallel_kernels(dp_mesh):
            o, vjp = jax.vjp(lambda q, k, v: gmflow.window_attention(
                q, k, v, 48, 64, True, jnp.bfloat16), q, k, v)
            return o, vjp(g)

    compiled = jax.jit(step).lower(x, x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    for out in jax.tree_util.tree_leaves(compiled.output_shardings):
        assert out.spec == P(DATA_AXIS), out


def test_lookup_partitions_over_data_mesh(dp_mesh):
    """The regression test for the default training configuration on more
    than one chip: with the batch sharded over ``data`` the lookup must
    lower per shard (``per_data_shard``), fwd and bwd — and the bare
    kernel must still be what GSPMD refuses, or the wrap has lost its
    reason."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from raft_tpu.ops.pallas_corr import (_pyramid_lookup,
                                          pallas_pyramid_lookup)
    from raft_tpu.parallel.mesh import DATA_AXIS, data_parallel_kernels

    _, _, pyramid, coords = _lookup_operands(
        8, 46, 62, NamedSharding(dp_mesh, P(DATA_AXIS)))

    def loss(lookup, pyr, c):
        return jnp.sum(lookup(pyr, c, 4, 128, False,
                              jnp.bfloat16).astype(jnp.float32))

    def step(pyr, c):
        with data_parallel_kernels(dp_mesh):
            return jax.value_and_grad(
                functools.partial(loss, pallas_pyramid_lookup))(pyr, c)

    compiled = jax.jit(step).lower(pyramid, coords).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text      # the loss, summed over the shards
    for level in compiled.output_shardings[1]:
        assert level.spec == P(DATA_AXIS), level  # dcorr stays sharded

    with pytest.raises(NotImplementedError, match="Mosaic"):
        jax.jit(functools.partial(loss, _pyramid_lookup)).lower(
            pyramid, coords)


def test_raft_full_eval_forward_compiles_for_v5e(one_chip, monkeypatch):
    """The program validate and serve run at the Sintel shape (436x1024
    padded to 440x1024, 32 iterations, bf16) compiles for one v5e, fits
    its 16 GB, and samples its pyramid with the Mosaic lookup: the
    default configuration, nothing asked for by name."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.config import RAFTConfig
    from raft_tpu.evaluate import make_inference_model

    # The program asks the backend which lookup to build, and here that
    # is the CPU the tests run on: answer for the chip being compiled for.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = make_inference_model(RAFTConfig.full(compute_dtype="bfloat16"))
    image = jax.ShapeDtypeStruct((1, 440, 1024, 3), jnp.float32,
                                 sharding=one_chip)
    small = jnp.zeros((1, 64, 96, 3), jnp.float32)
    rng = jax.random.PRNGKey(0)
    variables = _with_sharding(jax.eval_shape(
        lambda: model.init({"params": rng, "dropout": rng}, small, small,
                           iters=1)), one_chip)

    def fwd(v, a, b):
        return model.apply(v, a, b, iters=32, test_mode=True, train=False)

    compiled = jax.jit(fwd).lower(variables, image, image).compile()
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert max(need, ma.peak_memory_in_bytes) < 16 * 2 ** 30
    _, flow_up = compiled.out_info
    assert flow_up.shape == (1, 440, 1024, 2)
    assert "tpu_custom_call" in compiled.as_text()


def _serve_program_specs(cfg, one_chip, lanes=1, bucket=(440, 1024)):
    """``(variables, state, image, lane, scalar)`` shape specs on
    ``one_chip`` for the engine's programs of ``cfg`` at ``bucket``, plus
    the host template they were read off."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.models.raft import RAFT
    from raft_tpu.serve import slots

    small = jnp.zeros((1, 64, 96, 3), jnp.float32)
    rng = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(
        lambda: RAFT(cfg).init({"params": rng, "dropout": rng}, small,
                               small, iters=1))
    template = slots.state_template(cfg, shapes, lanes, bucket)

    def spec(tree):
        return _with_sharding(jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree),
            one_chip)

    image = jax.ShapeDtypeStruct((lanes,) + bucket + (3,), jnp.float32,
                                 sharding=one_chip)
    lane = lambda dt: jax.ShapeDtypeStruct((lanes,), dt, sharding=one_chip)
    scalar = lambda dt: jax.ShapeDtypeStruct((), dt, sharding=one_chip)
    return spec(shapes), spec(template), image, lane, scalar, template


def _loop_body(text):
    """``(inside, outside)``: the text of the one ``while`` loop's body
    with every computation it calls, and the rest of the module."""
    import re

    blocks = {}
    for m in re.finditer(r"^(?:ENTRY )?(%[\w.\-]+) [^\n]*\{\n(.*?)^\}",
                         text, re.M | re.S):
        blocks[m.group(1)] = m.group(2)
    loops = re.findall(r" while\([^\n]*?body=(%[\w.\-]+)", text)
    assert len(loops) == 1, loops
    inside, todo = set(), [loops[0]]
    while todo:
        name = todo.pop()
        if name in inside:
            continue
        inside.add(name)
        todo += [c for c in re.findall(r"%[\w.\-]+", blocks[name])
                 if c in blocks]
    return ("\n".join(blocks[n] for n in sorted(inside)),
            "\n".join(b for n, b in sorted(blocks.items())
                      if n not in inside))


def _hbm_copies(text, shape):
    """The instructions of ``text`` that write a copy of a ``shape``
    array to HBM: a ``copy`` or ``copy-done`` whose result layout names
    no memory space (``S(1)`` is the chip's VMEM: the prefetch of an
    operand for the operation that reads it, not a second array)."""
    import re

    return [m.group(0) for m in re.finditer(
        r"= " + re.escape(shape) + r"\{([^}]*)\} (?:copy|copy-done)\(",
        text) if "S(" not in m.group(1)]


def test_full_iter_program_is_one_device_loop_that_copies_no_pyramid(
        one_chip, monkeypatch):
    """``raft_full``'s iteration program at the Sintel bucket as the
    engine builds it (one lane, bf16, the step count a runtime scalar):
    the Mosaic lookup sits inside the body of a ``while``, and neither
    that body nor the rest of the program copies a level of the 132 MB
    pyramid — it is an input the loop only reads and the program does
    not return (the parent handed it back un-donated, a copy in every
    one of a request's 32 programs: PERF.md section 5)."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.config import RAFTConfig
    from raft_tpu.evaluate import make_inference_model
    from raft_tpu.serve import slots

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = make_inference_model(
        RAFTConfig.full(compute_dtype="bfloat16")).config
    variables, state, _, _, scalar, template = _serve_program_specs(
        cfg, one_chip)
    assert [tuple(a.shape) for a in template["corr"][:2]] == [
        (1, 55, 128, 7040), (1, 27, 64, 7040)]
    it = jax.jit(slots.make_iter_fn(cfg)).lower(
        variables, state, scalar(jnp.float32),
        scalar(jnp.int32)).compile()
    inside, outside = _loop_body(it.as_text())
    assert "tpu_custom_call" in inside
    assert "tpu_custom_call" not in outside
    for level in ("bf16[1,55,128,7040]", "bf16[1,27,64,7040]"):
        assert _hbm_copies(inside, level) == []
        assert _hbm_copies(outside, level) == []
    moved, flow_up = it.out_info
    assert flow_up.shape == (1, 440, 1024, 2)
    assert "corr" not in moved and moved["net"].shape == (1, 55, 128, 128)
    ma = it.memory_analysis()
    assert ma.output_size_in_bytes < 2 ** 23 < ma.argument_size_in_bytes


def test_gma_serve_programs_compile_for_v5e(one_chip, monkeypatch):
    """arch 'gma' at the Sintel bucket, as the engine would build it (one
    lane, bf16): ``encode_admit`` and ``iter_step`` compile for one v5e
    and fit it, the slot state holds the ``(N, N)`` attention beside the
    pyramid (bf16, 99 MB a lane at 55x128), and the iteration still
    samples the pyramid with the Mosaic lookup.  No cell serves GMA yet
    (PERF.md section 7): this is what keeps that path compiling."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.config import RAFTConfig
    from raft_tpu.evaluate import make_inference_model
    from raft_tpu.serve import slots

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = make_inference_model(
        RAFTConfig.gma(compute_dtype="bfloat16")).config
    variables, state, image, lane, scalar, template = \
        _serve_program_specs(cfg, one_chip)
    n = 55 * 128
    assert template["attn"].shape == (1, n, n)
    assert template["attn"].nbytes == 2 * n * n
    enc = jax.jit(slots.make_encode_fn(cfg)).lower(
        variables, image, image, state, lane(jnp.bool_),
        lane(jnp.int32)).compile()
    it = jax.jit(slots.make_iter_fn(cfg)).lower(
        variables, state, scalar(jnp.float32),
        scalar(jnp.int32)).compile()
    for compiled in (enc, it):
        ma = compiled.memory_analysis()
        need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes)
        assert max(need, ma.peak_memory_in_bytes) < 16 * 2 ** 30
    # the loop reads the attention as it reads the pyramid: the lookup
    # is in its body, and the program copies neither anywhere (VMEM
    # prefetches of the aggregate's operand aside) nor returns them
    inside, outside = _loop_body(it.as_text())
    assert "tpu_custom_call" in inside
    for leaf in (f"bf16[1,{n},{n}]", "bf16[1,55,128,7040]"):
        assert _hbm_copies(inside, leaf) == []
        assert _hbm_copies(outside, leaf) == []
    moved, flow_up = it.out_info
    assert "attn" not in moved and "corr" not in moved
    assert enc.out_info["attn"].shape == (1, n, n)
    assert flow_up.shape == (1, 440, 1024, 2)
