"""Which lookup samples a materialized correlation pyramid, and that the
serve programs agree whichever it is (tier-1, CPU).

One function decides, from platform and map shape
(``ops/pallas_corr.pyramid_lookup_path``); ``models.raft.corr_impl_at``
feeds it what the process can observe, and everything that builds or
samples the pyramid asks there while it traces.  Pinned here:

- the choice itself over platform x shape x radius x storage;
- what the model hands it (backend, the interpreter stand-in, rows split
  over devices, on-demand implementations untouched), and that the
  fused lookup+encoder and the train step under either name follow it;
- the forward kernel an undifferentiated call runs (rolled up, short to
  trace) against the one a differentiated call keeps;
- the serve program pair (``encode_admit`` + ``iter_step``) built with
  the Mosaic lookup (interpreted) against the XLA pair, both radii;
- ``engine.stats()["lookup"]``, the ``compile`` ring's ``program`` record,
  and the AOT artifact's refusal of programs built with another lookup.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.config import RAFTConfig
from raft_tpu.models.raft import corr_impl_at
from raft_tpu.ops.pallas_corr import (_PYR_LOOKUP_BUDGET,
                                      pyramid_lookup_path,
                                      pyramid_lookup_vmem_bytes)


# (platform, h8, w8, radius, block_q, storage bytes) -> path.  46x62 is
# the chairs crop (368x496), 55x128 Sintel (440x1024), 136x240 1088x1920.
@pytest.mark.parametrize("platform,h8,w8,radius,block_q,store,want", [
    ("tpu", 46, 62, 4, 128, 2, "mosaic"),
    ("tpu", 46, 62, 3, 128, 2, "mosaic"),
    ("tpu", 55, 128, 4, 128, 2, "mosaic"),
    ("tpu", 55, 128, 3, 128, 2, "mosaic"),
    ("tpu", 55, 128, 4, 128, 4, "mosaic"),     # fp32 compute stores fp32
    ("tpu", 55, 128, 4, 128, 1, "mosaic"),     # int8 / fp8 storage
    ("tpu", 136, 240, 4, 128, 2, "mosaic"),    # 24 MiB: inside the budget
    ("tpu", 136, 240, 4, 512, 4, "xla"),       # 179 MiB: over it
    ("tpu", 272, 480, 4, 128, 4, "xla"),       # 2176x3840 fp32: over it
    ("cpu", 46, 62, 4, 128, 2, "xla"),
    ("cpu", 55, 128, 3, 128, 2, "xla"),
    ("gpu", 55, 128, 4, 128, 2, "xla"),
])
def test_lookup_path_by_platform_and_shape(platform, h8, w8, radius,
                                           block_q, store, want):
    got = pyramid_lookup_path(platform, h8, w8, levels=4, radius=radius,
                              block_q=block_q, storage_bytes=store)
    assert got == want
    if platform == "tpu":
        fits = pyramid_lookup_vmem_bytes(
            h8, w8, 4, radius, block_q, store) <= _PYR_LOOKUP_BUDGET
        assert fits == (want == "mosaic")
    # image rows split over devices: never a whole-image kernel
    assert pyramid_lookup_path(platform, h8, w8, levels=4, radius=radius,
                               block_q=block_q, storage_bytes=store,
                               rows_split=True) == "xla"


def test_lookup_residency_counts_the_level0_block_twice():
    """The figure the budget is held against is dominated by level 0's
    double-buffered block: 136x240x128 bf16 is 8.4 MB, twice that in
    flight (ISSUE 27)."""
    block = 136 * 240 * 128 * 2
    total = pyramid_lookup_vmem_bytes(136, 240, 4, 4, 128, 2)
    assert 2 * block < total < 3.2 * block


@pytest.mark.parametrize("case", [
    "cpu_default", "cpu_named_fallback", "cpu_named_interpret",
    "cpu_default_interpret", "tpu_default", "tpu_named", "tpu_small",
    "tpu_over_budget", "tpu_rows_split", "tpu_chunked", "cpu_pallas"])
def test_model_feeds_the_selection_what_it_observes(case, monkeypatch):
    from raft_tpu.parallel.mesh import data_parallel_kernels

    if case.startswith("tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    full = RAFTConfig.full
    cfg, hw, want = {
        # off TPU as ever: XLA, and the kernel only where a test asks for
        # the interpreter by the kernel's name
        "cpu_default": (full(), (55, 128), "allpairs"),
        "cpu_named_fallback": (full(corr_impl="allpairs_pallas"),
                               (55, 128), "allpairs"),
        "cpu_named_interpret": (full(corr_impl="allpairs_pallas",
                                     pallas_offtpu="interpret"),
                                (8, 12), "allpairs_pallas"),
        "cpu_default_interpret": (full(pallas_offtpu="interpret"),
                                  (8, 12), "allpairs"),
        # on TPU the default configuration runs the kernel, at the train
        # crop and at the serve shape, for both radii
        "tpu_default": (full(compute_dtype="bfloat16"), (55, 128),
                        "allpairs_pallas"),
        "tpu_named": (full(corr_impl="allpairs_pallas",
                           compute_dtype="bfloat16"), (46, 62),
                      "allpairs_pallas"),
        "tpu_small": (RAFTConfig.small_model(compute_dtype="bfloat16"),
                      (55, 128), "allpairs_pallas"),
        "tpu_over_budget": (full(corr_impl="allpairs_pallas",
                                 lookup_block_q=512), (136, 240),
                            "allpairs"),
        "tpu_rows_split": (full(), (46, 62), "allpairs"),
        # on-demand implementations are not this function's to choose
        "tpu_chunked": (full(corr_impl="chunked"), (55, 128), "chunked"),
        "cpu_pallas": (full(corr_impl="pallas"), (55, 128), "chunked"),
    }[case]
    with data_parallel_kernels(None, rows_split=case == "tpu_rows_split"):
        assert corr_impl_at(cfg, *hw) == want


def test_inference_model_and_auto_keep_corr_impl_as_given():
    """No entry point rewrites ``corr_impl`` any more: the inference
    model passes it through (it used to map the kernel back to XLA), and
    ``--corr_impl auto`` is the config's own default."""
    from raft_tpu.cli.train import default_corr_impl
    from raft_tpu.evaluate import make_inference_model

    for impl in ("allpairs", "allpairs_pallas", "chunked"):
        model = make_inference_model(RAFTConfig.full(corr_impl=impl))
        assert model.config.corr_impl == impl
        assert model.config.scan_unroll == 1
    assert default_corr_impl() == RAFTConfig().corr_impl == "allpairs"


def _pallas_kernels(jaxpr):
    """Names of the kernel functions of every ``pallas_call`` in a
    jaxpr, sub-jaxprs included."""
    from jax._src import core

    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["jaxpr"].debug_info.func_name)
        for sub in core.jaxprs_in_params(eqn.params):
            names += _pallas_kernels(sub)
    return names


def _lookup_case(h8, w8, store, field, seed):
    """A pyramid and a coordinate field the rolled forward is sensitive
    to: ``(flat pyramid, the same stored values query-major, coords)``."""
    from raft_tpu.ops.corr import build_corr_pyramid_flat

    rng = np.random.default_rng(seed)
    B, N = 2, h8 * w8
    f1 = jnp.asarray(rng.normal(size=(B, h8, w8, 32)), jnp.float32)
    f2 = jnp.asarray(rng.normal(size=(B, h8, w8, 32)), jnp.float32)
    pyr = build_corr_pyramid_flat(f1, f2, num_levels=4, pad_q=128,
                                  out_dtype=store)
    # the XLA sampler's layout over the very same stored values
    major = [jnp.moveaxis(lv[..., :N], 3, 1) for lv in pyr]
    ys, xs = np.meshgrid(np.arange(h8), np.arange(w8), indexing="ij")
    grid = np.stack([xs, ys], -1)[None].repeat(B, 0).astype(np.float32)
    coords = grid.copy()
    if field == "noisy":         # spread ~10 rows, some windows cut
        coords += rng.normal(scale=3.0, size=coords.shape)
        coords[0, 0, 0] = (-50.0, -50.0)        # windows wholly outside
        coords[1, -1, -1] = (500.0, 500.0)
    elif field == "constant":    # every window starts on one row
        coords[...] = (w8 / 2 + 0.3, h8 / 2 - 0.4)
    elif field == "straddle":    # no flow: a block spans 128 / w8 rows
        coords += 0.25
    elif field == "integral":    # cy on a row: the upper weight is 0
        coords += np.float32([2.0, -1.0])
    elif field == "above":
        coords[..., 1] = -50.0 + 0.1 * ys[None]
    elif field == "below":
        coords[..., 1] = h8 + 40.0 + 0.1 * ys[None]
    elif field == "left":
        coords[..., 0] = -300.0
    elif field == "far_beside_near":
        # real queries as far out as padded ones (-1e6) in every block,
        # beside queries whose windows lie on the map
        coords += rng.normal(scale=0.7, size=coords.shape)
        coords[:, :, ::5] = -1e6
        coords[:, ::3, 1::5, 1] = 1e6
    else:
        raise AssertionError(field)
    return pyr, major, jnp.asarray(coords, jnp.float32)


@pytest.mark.parametrize("h8,w8,radius,store,field", [
    (8, 12, 4, jnp.float32, "noisy"),       # levels 8x12 .. 1x1
    (16, 24, 3, jnp.bfloat16, "noisy"),     # radius 3 (k = 7), bf16 storage
    (23, 31, 4, jnp.bfloat16, "noisy"),     # odd rows, 6 blocks, the last
                                            # with 73 real + 55 padded lanes
    (4, 6, 4, jnp.float32, "noisy"),        # over-pooled: last level empty
    (16, 24, 4, jnp.float32, "constant"),
    (16, 24, 4, jnp.bfloat16, "straddle"),
    (23, 31, 3, jnp.float32, "straddle"),
    (16, 24, 4, jnp.float32, "integral"),
    (16, 24, 4, jnp.float32, "above"),
    (16, 24, 3, jnp.bfloat16, "below"),
    (16, 24, 4, jnp.float32, "left"),
    (23, 31, 4, jnp.float32, "far_beside_near"),
])
def test_rolled_forward_is_the_unrolled_forward(h8, w8, radius, store,
                                                field):
    """A call no gradient is asked of runs the rolled-up kernel (short to
    trace and lower, PERF.md section 6 PR 27; since PR 33 its y stage
    gathers the rows each window reaches), a differentiated one keeps the
    unrolled kernel the train cells run: same taps from both, to fp32
    rounding, and from the XLA sampler over the same stored values."""
    from raft_tpu.ops import pallas_corr as pc
    from raft_tpu.ops.corr import corr_lookup

    pyr, major, coords = _lookup_case(h8, w8, store, field, seed=h8)

    def lookup(p, c):
        return pc.pallas_pyramid_lookup(p, c, radius, 128, True,
                                        jnp.float32)

    assert _pallas_kernels(jax.make_jaxpr(lookup)(pyr, coords).jaxpr) \
        == ["_pyr_multi_fwd_rolled_kernel"]
    rolled = jax.jit(lookup)(pyr, coords)
    # what a differentiated call's forward rule runs (the train-step
    # test below finds it in a traced step)
    unrolled, _ = jax.jit(lambda p, c: pc._pyr_fwd(
        p, c, radius, 128, True, jnp.float32))(pyr, coords)
    if field in ("above", "below", "left"):
        assert float(jnp.abs(unrolled).max()) == 0.0
    else:
        assert float(jnp.abs(unrolled).max()) > 1.0
    np.testing.assert_allclose(np.asarray(rolled), np.asarray(unrolled),
                               rtol=0, atol=2e-5)
    xla = jax.jit(lambda p, c: corr_lookup(p, c, radius))(major, coords)
    np.testing.assert_allclose(np.asarray(rolled), np.asarray(xla),
                               rtol=0, atol=1e-4)


@pytest.mark.parametrize("h8,w8,radius,field", [
    (16, 24, 4, "constant"), (16, 24, 4, "straddle"),
    (23, 31, 3, "noisy"), (23, 31, 4, "far_beside_near"),
    (16, 24, 4, "above"), (16, 24, 3, "integral"),
])
def test_lookup_reach_is_the_rows_the_kernel_reads(h8, w8, radius, field):
    """``lookup_reach`` is the counter the kernel cannot return.  Held
    against the kernel itself: rows outside ``[first, first + rows)`` of
    a block may hold anything (NaN here) and the taps do not change;
    a NaN in the first or the last reached row of a block reaches its
    taps; and the passes are the distinct window starts of the block."""
    from raft_tpu.ops import pallas_corr as pc

    pyr, _, coords = _lookup_case(h8, w8, jnp.float32, field, seed=w8)
    reach = pc.lookup_reach(coords, (h8, w8), 4, radius, 128)
    k, blocks = 2 * radius + 1, pyr[0].shape[3] // 128
    lookup = jax.jit(lambda p, c: pc.pallas_pyramid_lookup(
        p, c, radius, 128, True, jnp.float32))
    clean = np.asarray(lookup(pyr, coords))
    assert np.isfinite(clean).all()

    outside, edges = [], []
    for lvl, (level, got) in enumerate(zip(pyr, reach)):
        hl = level.shape[1]
        first, rows, passes = (np.asarray(got[key]) for key in
                               ("first", "rows", "passes"))
        assert first.shape == (2, blocks) and (got["held"] == hl).all()
        assert ((rows == 0) == (passes == 0)).all() or not hl
        assert (rows <= np.minimum(hl, k + passes)).all()
        # by hand: the distinct floor(cy) among windows that touch the map
        cy = np.full((2, blocks * 128), -1e6, np.float32)
        cy[:, :h8 * w8] = np.asarray(coords)[..., 1].reshape(2, -1)
        y0 = np.floor(cy / 2.0 ** lvl).reshape(2, blocks, 128)
        for b, i in np.ndindex(2, blocks):
            live = y0[b, i][(y0[b, i] >= -radius - 1)
                            & (y0[b, i] <= hl + radius - 1)]
            want = int(live.max() - live.min()) + 1 if live.size and hl \
                else 0
            assert passes[b, i] == want, (lvl, b, i)
        row = np.arange(hl)[None, :, None, None]
        lo = np.repeat(first, 128, axis=1)[:, None, None, :]
        hi = lo + np.repeat(rows, 128, axis=1)[:, None, None, :]
        out = (row < lo) | (row >= hi)
        outside.append(jnp.where(out, jnp.nan, level))
        edge = ((row == lo) | (row == hi - 1)) & ~out
        edges.append(jnp.where(edge, jnp.nan, level))
    np.testing.assert_array_equal(np.asarray(lookup(outside, coords)), clean)
    hit = np.isnan(np.asarray(lookup(edges, coords)))      # (B, H, W, L*k*k)
    hit = hit.reshape(2, h8 * w8, 4, k * k).any(axis=3)
    for lvl, got in enumerate(reach):
        reached = np.repeat(np.asarray(got["rows"]) > 0, 128,
                            axis=1)[:, :h8 * w8]
        lanes = np.add.reduceat(hit[:, :, lvl], np.arange(
            0, h8 * w8, 128), axis=1) > 0               # any lane a block
        np.testing.assert_array_equal(
            lanes, np.asarray(got["rows"]) > 0, err_msg=f"level {lvl}")
        assert not hit[:, :, lvl][~reached].any()


def test_rolled_kernel_stays_short_to_trace():
    """jax traces and lowers a Mosaic kernel again in every process for
    every program that holds it, so the kernel's length is ``setup_s``
    (PERF.md section 6, PR 27 and PR 33): the rolled forward at four
    levels, radius 4, is 779 equations by this count (253 before PR 33
    gave it a gather of x tiles and static loops over them and the x
    offsets, worth 0.06 ms an iteration), an eighth of the unrolled
    one; a change that lengthens it has to show what the length buys."""
    from jax._src import core

    from raft_tpu.ops import pallas_corr as pc

    def count(jaxpr):
        return sum(1 + sum(count(sub) for sub in
                           core.jaxprs_in_params(eqn.params))
                   for eqn in jaxpr.eqns)

    def kernels(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield count(eqn.params["jaxpr"])
            for sub in core.jaxprs_in_params(eqn.params):
                yield from kernels(sub)

    pyr = [jax.ShapeDtypeStruct((1, 55 >> lvl, 128 >> lvl, 7040),
                                jnp.bfloat16) for lvl in range(4)]
    coords = jax.ShapeDtypeStruct((1, 55, 128, 2), jnp.float32)
    rolled, = kernels(jax.make_jaxpr(lambda p, c: pc.pallas_pyramid_lookup(
        p, c, 4, 128, True, jnp.bfloat16))(pyr, coords).jaxpr)
    unrolled, = kernels(jax.make_jaxpr(lambda p, c: pc._pyr_fwd(
        p, c, 4, 128, True, jnp.bfloat16)[0])(pyr, coords).jaxpr)
    assert rolled <= 900, rolled
    assert unrolled > 6 * rolled, (rolled, unrolled)


def _trace_train_step(model_cfg, monkeypatch, H=48, W=64, B=2):
    from raft_tpu.config import TrainConfig
    from raft_tpu.models.raft import RAFT
    from raft_tpu.train.optim import make_optimizer
    from raft_tpu.train.step import init_state, make_train_step

    model = RAFT(model_cfg)
    cfg = TrainConfig(num_steps=10, batch_size=B, image_size=(H, W),
                      iters=2)
    tx = make_optimizer(cfg.lr, cfg.num_steps, cfg.wdecay, cfg.epsilon,
                        cfg.clip)
    key = jax.random.PRNGKey(0)
    state = jax.eval_shape(lambda: init_state(model, tx, key, (H, W)))
    S = jax.ShapeDtypeStruct
    batch = {"image1": S((B, H, W, 3), jnp.float32),
             "image2": S((B, H, W, 3), jnp.float32),
             "flow": S((B, H, W, 2), jnp.float32),
             "valid": S((B, H, W), jnp.float32)}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = make_train_step(model, tx, cfg, None, donate=False)
    return jax.make_jaxpr(step)(state, batch, key)


def test_train_step_is_one_program_under_either_name(monkeypatch):
    """``--corr_impl auto`` hands the model 'allpairs' now where it
    handed 'allpairs_pallas' on a TPU: with the backend answering for
    the chip the two trace to the same step, whose forward is the
    unrolled kernel (traced only; the train cell's own size is compared
    across commits by the command PERF.md section 6 names)."""
    small = RAFTConfig.small_model
    auto = _trace_train_step(small(scan_unroll=1), monkeypatch)
    named = _trace_train_step(
        small(scan_unroll=1, corr_impl="allpairs_pallas"), monkeypatch)
    import re

    text = [re.sub(r" at 0x[0-9a-f]+", "", str(j)) for j in (auto, named)]
    assert text[0] == text[1]
    kernels = _pallas_kernels(auto.jaxpr)
    assert "_pyr_multi_fwd_kernel" in kernels
    assert "_pyr_multi_bwd_kernel" in kernels
    assert "_pyr_multi_fwd_rolled_kernel" not in kernels


@pytest.mark.parametrize("backend,want", [("tpu", True), ("cpu", False)])
def test_fused_lookup_encoder_follows_the_selection(backend, want,
                                                    monkeypatch):
    """``fused_lookup_encoder`` samples the Mosaic lookup's pyramid, so
    it engages exactly where the selection picks that lookup -- under
    the default ``corr_impl`` too, which is what ``--corr_impl auto``
    hands the model (it used to ask for 'allpairs_pallas' by name)."""
    import warnings

    from raft_tpu.models.raft import RAFT

    cfg = RAFTConfig.small_model(fused_lookup_encoder=True)
    model = RAFT(cfg)
    img = jax.ShapeDtypeStruct((1, 64, 96, 3), jnp.float32)
    key = jax.random.PRNGKey(0)
    variables = jax.eval_shape(
        lambda: RAFT(RAFTConfig.small_model()).init(
            {"params": key, "dropout": key}, jnp.zeros((1, 64, 96, 3)),
            jnp.zeros((1, 64, 96, 3)), iters=1))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        jaxpr = jax.make_jaxpr(
            lambda v, a, b: model.apply(v, a, b, iters=1, test_mode=True))(
                variables, img, img)
    assert ("_pyr_encode_kernel" in _pallas_kernels(jaxpr.jaxpr)) is want


# ---------------------------------------------------------------------------
# The serve program pair with the Mosaic lookup against the XLA pair
# ---------------------------------------------------------------------------

BUCKET = (64, 96)      # -> 8x12 maps; levels 8x12, 4x6, 2x3, 1x1


@pytest.mark.parametrize("small", [False, True],
                         ids=["full_radius4", "small_radius3"])
def test_serve_programs_mosaic_lookup_matches_xla(small):
    """``encode_admit`` + one ``iter_step`` of two steps (the kernel
    inside the device loop), the kernel in the Pallas interpreter
    against the XLA programs: same state but for the
    pyramid's layout, same flow."""
    from raft_tpu.models.raft import RAFT
    from raft_tpu.serve import slots

    mk = RAFTConfig.small_model if small else RAFTConfig.full
    xla = mk()
    mosaic = mk(corr_impl="allpairs_pallas", pallas_offtpu="interpret")
    H, W = BUCKET
    assert corr_impl_at(xla, H // 8, W // 8) == "allpairs"
    assert corr_impl_at(mosaic, H // 8, W // 8) == "allpairs_pallas"

    rng = np.random.default_rng(27)
    a1 = jnp.asarray(rng.uniform(0, 255, (2, H, W, 3)), jnp.float32)
    # frame 2 is frame 1 moved: flows with something to look up
    a2 = jnp.roll(a1, (1, 2), axis=(1, 2))
    key = jax.random.PRNGKey(0)
    variables = RAFT(xla).init({"params": key, "dropout": key},
                               a1[:1], a2[:1], iters=1)
    admit = jnp.ones((2,), jnp.bool_)
    budgets = jnp.full((2,), 2, jnp.int32)
    out = {}
    for name, cfg in (("xla", xla), ("mosaic", mosaic)):
        state = slots.state_template(cfg, variables, 2, BUCKET)
        state = jax.jit(slots.make_encode_fn(cfg))(
            variables, a1, a2, state, admit, budgets)
        moved, flow_up = jax.jit(slots.make_iter_fn(cfg))(
            variables, state, jnp.float32(0.0), jnp.int32(2))
        state = slots.advance(state, moved)
        assert not np.asarray(state["active"]).any()
        out[name] = (state, np.asarray(flow_up))
    sx, fx = out["xla"]
    sm, fm = out["mosaic"]
    # the layouts differ as the lookups want them: (B, N, h, w) against
    # (B, h, w, Npad)
    assert sx["corr"][0].shape == (2, 96, 8, 12)
    assert sm["corr"][0].shape == (2, 8, 12, 128)
    assert np.abs(fx).max() > 0.1
    np.testing.assert_allclose(fm, fx, rtol=1e-4, atol=1e-4)
    for leaf in ("net", "coords1", "delta_max"):
        np.testing.assert_allclose(np.asarray(sm[leaf]),
                                   np.asarray(sx[leaf]),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# What the engine says of it
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine():
    from raft_tpu.models.raft import RAFT
    from raft_tpu.serve import InferenceEngine, ServeConfig

    cfg = RAFTConfig.small_model()
    img = jnp.zeros((1, 40, 56, 3))
    key = jax.random.PRNGKey(0)
    variables = RAFT(cfg).init({"params": key, "dropout": key}, img, img,
                               iters=1)
    eng = InferenceEngine(variables, cfg, ServeConfig(
        iters=2, batch_sizes=(1,), max_batch=1, max_wait_ms=1))
    eng.start()
    yield eng
    eng.stop()


def test_engine_names_the_lookup_of_every_program(engine):
    from raft_tpu.obs import stages

    before = len(stages.recent("compile"))
    assert engine.stats()["lookup"] == {}
    rng = np.random.default_rng(0)
    flow = engine.infer(
        rng.uniform(0, 255, (36, 52, 3)).astype(np.float32),
        rng.uniform(0, 255, (36, 52, 3)).astype(np.float32), timeout=300)
    assert np.isfinite(flow).all()
    # on the CPU the default configuration is the XLA lookup
    assert engine.stats()["lookup"] == {"40x56/b1": "xla"}
    mine = [r for r in stages.recent("compile")[before:]
            if r["kind"] == "program"]
    assert [(r["name"], r["lookup"]) for r in mine] == [
        ("40x56/b1/iter", "xla")]
    assert mine[0]["seconds"] > 0 and mine[0]["imported"] is False


def test_aot_artifact_is_held_to_the_importers_lookup(engine, tmp_path):
    """The fingerprint hashes the config, and the lookup is no longer in
    it: the artifact's keys carry the correlation implementation their
    bucket resolved to, and an engine whose model resolves the bucket to
    another one refuses them."""
    import json

    from raft_tpu.serve import aot

    if not engine.compiled_keys():
        pytest.skip("the engine compiled nothing (test order)")
    manifest = engine.export_aot(str(tmp_path))
    assert {k["corr_impl"] for k in manifest["keys"]} == {"allpairs"}
    fp = manifest["fingerprint"]
    exes = aot.import_executables(str(tmp_path), fingerprint=fp,
                                  corr_impl=engine._corr_impl_at)
    assert set(exes) == set(engine.compiled_keys())
    with pytest.raises(aot.AOTImportError, match="built with corr_impl"):
        aot.import_executables(
            str(tmp_path), fingerprint=fp,
            corr_impl=lambda bucket: "allpairs_pallas")
    # an artifact from before the keys carried it is refused as well
    path = tmp_path / aot.MANIFEST
    old = json.loads(path.read_text())
    for k in old["keys"]:
        del k["corr_impl"]
    path.write_text(json.dumps(old))
    with pytest.raises(aot.AOTImportError, match="built with corr_impl"):
        aot.import_executables(str(tmp_path), fingerprint=fp,
                               corr_impl=engine._corr_impl_at)


def test_aot_artifact_of_another_iteration_call_is_refused_by_name(
        engine, tmp_path):
    """The iteration program took its step count at run time in PR 29:
    an artifact records how each program is called, and one whose
    ``iter`` was built for the earlier call — a manifest from before the
    field existed, as the parent commit wrote them, or one that names
    another argument list — is refused with the program and both calls
    spelled out, not imported and called with the wrong arguments."""
    import json

    from raft_tpu.serve import InferenceEngine, ServeConfig, aot

    if not engine.compiled_keys():
        pytest.skip("the engine compiled nothing (test order)")
    manifest = engine.export_aot(str(tmp_path))
    calls = engine._program_calls()
    assert calls["iter"] == "iter_step(variables, state, threshold, steps)"
    assert {k["program"]: k["call"] for k in manifest["keys"]} == {
        "enc": calls["enc"], "iter": calls["iter"]}
    fp = manifest["fingerprint"]
    assert set(aot.import_executables(
        str(tmp_path), fingerprint=fp, calls=calls)) == set(
            engine.compiled_keys())
    path = tmp_path / aot.MANIFEST
    parents = json.loads(path.read_text())
    for k in parents["keys"]:
        del k["call"]
    path.write_text(json.dumps(parents))
    refusal = (r"exe-40x56-b1-iter\.bin is the program 'iter' built to "
               r"be called as iter_step\(variables, state, threshold\); "
               r"this engine calls iter_step\(variables, state, "
               r"threshold, steps\)")
    with pytest.raises(aot.AOTImportError, match=refusal):
        aot.import_executables(str(tmp_path), fingerprint=fp, calls=calls)
    # an engine pointed at it falls back to building its own programs
    cold = InferenceEngine(engine._variables, RAFTConfig.small_model(),
                           ServeConfig(iters=2, batch_sizes=(1,),
                                       max_batch=1,
                                       aot_dir=str(tmp_path)))
    assert cold.aot_info["ok"] is False and cold.aot_info["imported"] == 0
    assert "'iter'" in cold.aot_info["error"]
    # and any other recorded call is held to the importer's likewise
    for k in parents["keys"]:
        k["call"] = calls[k["program"]].replace("budgets", "budget")
    path.write_text(json.dumps(parents))
    with pytest.raises(aot.AOTImportError, match="program 'enc' built"):
        aot.import_executables(str(tmp_path), fingerprint=fp, calls=calls)


def test_cost_of_a_pair_is_enc_plus_steps_times_one_loop_body(engine):
    """XLA counts a loop of unknown length once, so the ``iter`` entry of
    the cost ledger is ONE refinement step whatever ``steps`` a call
    passes: a pair costs ``enc + iters x iter`` as before, and
    ``python -m raft_tpu cost`` stamps the same two programs."""
    from raft_tpu.cli import cost as cli
    from raft_tpu.models.raft import RAFTIterStep, RAFTUpsample

    if not engine.compiled_keys():
        pytest.skip("the engine compiled nothing (test order)")
    bucket, cfg, v = (40, 56), engine._model_cfg, engine._variables
    enc = engine.cost_book.get((bucket, 1, "enc"))
    it = engine.cost_book.get((bucket, 1, "iter"))
    attrs = engine._pipeline_cost_attrs(bucket, 1, 2, 0.1)
    assert attrs["flops"] == enc.flops + 2 * it.flops
    # one body: the step and the guarded upsample, once
    tpl = engine._programs[(bucket, 1)].template

    def one_step(variables, state):
        net, coords1 = RAFTIterStep(cfg).apply(
            variables, state["net"], state["coords1"], state["inp"],
            state["coords0"], state["corr"])
        return RAFTUpsample(cfg).apply(variables, net,
                                       coords1 - state["coords0"])

    body = jax.jit(one_step).lower(v, tpl).compile().cost_analysis()
    assert 0.98 * body["flops"] < it.flops < 1.1 * body["flops"]
    # the CLI lowers the same functions with the same arguments
    rows = cli.serve_costs(cfg, v, bucket, 1)
    assert [c.program for c in rows] == ["serve_enc_40x56_b1",
                                         "serve_iter_40x56_b1"]
    assert rows[0].flops == enc.flops and rows[1].flops == it.flops


def test_spatially_sharded_step_keeps_the_xla_lookup_on_tpu(monkeypatch):
    """GSPMD cannot split a Mosaic call over image rows, and a trace sees
    no shardings: ``make_train_step(shard_spatial=True)`` tells the
    selection through ``data_parallel_kernels(rows_split=True)``.  Traced
    only (a jaxpr), with the backend answering for the chip: the
    data-parallel step holds the kernel, the row-split step does not."""
    from raft_tpu.config import TrainConfig
    from raft_tpu.models.raft import RAFT
    from raft_tpu.parallel.mesh import make_mesh
    from raft_tpu.train.optim import make_optimizer
    from raft_tpu.train.step import init_state, make_train_step

    if jax.device_count() < 4:
        pytest.skip("needs 4 virtual devices")
    H, W, B = 48, 64, 4
    model = RAFT(RAFTConfig.small_model(scan_unroll=1))
    cfg = TrainConfig(num_steps=10, batch_size=B, image_size=(H, W),
                      iters=2)
    tx = make_optimizer(cfg.lr, cfg.num_steps, cfg.wdecay, cfg.epsilon,
                        cfg.clip)
    key = jax.random.PRNGKey(0)
    state = jax.eval_shape(lambda: init_state(model, tx, key, (H, W)))
    S = jax.ShapeDtypeStruct
    batch = {"image1": S((B, H, W, 3), jnp.float32),
             "image2": S((B, H, W, 3), jnp.float32),
             "flow": S((B, H, W, 2), jnp.float32),
             "valid": S((B, H, W), jnp.float32)}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    traced = {}
    for name, mesh, split in (
            ("data", make_mesh(num_data=4, num_spatial=1,
                               devices=jax.devices()[:4]), False),
            ("rows", make_mesh(num_data=2, num_spatial=2,
                               devices=jax.devices()[:4]), True)):
        step = make_train_step(model, tx, cfg, mesh, donate=False,
                               shard_spatial=split)
        traced[name] = str(jax.make_jaxpr(step)(state, batch, key))
    assert "pallas_call" in traced["data"]
    assert "pallas_call" not in traced["rows"]
