"""Pattern-search subsystem tests (DESIGN.md §16): template rendering
properties, the versioned per-(layer, head) artifact (round-trip,
corrupt-file and schema-mismatch recovery — for both the pattern
loader and the hardened autotune cache loader), the static plan-once
policy (bitwise parity with the manually-driven sparse backend, single
cache refresh per trajectory), the rainfusion tri-branch routing, the
offline search's static/dynamic classification, and spatial-only
patterns on T=1 image grids."""

import dataclasses
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.config.base import RippleConfig
from repro.core import dispatch, patterns
from repro.core.decision_cache import initial_state, supports_cache
from repro.core.dispatch import attention_dispatch
from repro.core.policy import get_policy, list_policies
from repro.kernels.sparse.ops import (PARTIAL, SKIP, block_map_from_keep,
                                      sparse_attention_pallas,
                                      sparse_block_stats)

GRIDS = [(1, 4, 4), (2, 4, 4), (1, 8, 8), (4, 8, 8), (3, 5, 7)]


def _qkv(seed, shape):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, shape) for k in ks)


def _toy_artifact(grid=(4, 8, 8), block=(32, 32)):
    """Hand-built artifact: head 0 static temporal, head 1 static
    spatial, head 2 dynamic."""
    t_spec = patterns.template("frame_diag", window=1, sink=1)
    s_spec = patterns.template("spatial_local", radius=1)
    heads = {
        (0, 0): patterns.HeadAssignment(
            spec=t_spec, static=True, branch="spatial", psnr_db=40.0,
            skip_rate=patterns.template_skip_rate(t_spec, grid, block),
            stability=1.0),
        (0, 1): patterns.HeadAssignment(
            spec=s_spec, static=True, branch="spatial", psnr_db=35.0,
            skip_rate=patterns.template_skip_rate(s_spec, grid, block),
            stability=1.0),
        (0, 2): patterns.HeadAssignment(
            spec=patterns.template("dense"), static=False,
            branch="dynamic", psnr_db=0.0, skip_rate=0.0, stability=0.4),
    }
    return patterns.PatternArtifact(grid=grid, block_shape=block,
                                    tolerance_db=30.0, heads=heads)


class TestTemplateProperties:
    """Satellite: every template renders a valid block map across
    grids and block shapes (fixed examples without hypothesis)."""

    @settings(deadline=None, max_examples=30)
    @given(gi=st.integers(0, len(GRIDS) - 1), bq=st.integers(1, 48),
           bk=st.integers(1, 48))
    def test_bank_renders_valid_maps(self, gi, bq, bk):
        grid = GRIDS[gi]
        n = grid[0] * grid[1] * grid[2]
        for spec in patterns.default_bank(grid):
            keep = patterns.render_keep(spec, grid)
            assert keep.shape == (n, n)
            assert keep.dtype == np.bool_
            # no template may mask a token's own key
            assert keep.diagonal().all()

            bm = patterns.block_map_np(keep, bq, bk)
            cq, ck = min(bq, n), min(bk, n)
            assert bm.shape == (-(-n // cq), -(-n // ck))
            assert bm.dtype == np.int32
            # the kept diagonal means no q-row of tiles is all-SKIP
            assert (bm != SKIP).any(axis=-1).all()
            # tile states consistent with the mask, via parity with the
            # kernel's own tiling (edge padding included)
            jm = np.asarray(block_map_from_keep(jnp.asarray(keep), bq, bk))
            np.testing.assert_array_equal(bm, jm)

    def test_dense_template_is_all_full(self):
        bm = patterns.render_block_map(patterns.template("dense"),
                                       (2, 4, 4), (16, 16))
        assert (bm == 1).all()  # FULL everywhere, zero skip
        assert patterns.template_skip_rate(
            patterns.template("dense"), (2, 4, 4), (16, 16)) == 0.0

    def test_unknown_family_raises(self):
        with pytest.raises(ValueError, match="unknown template family"):
            patterns.template("nope")

    def test_image_grid_spatial_pattern_beats_dense_on_skip(self):
        # satellite: T=1 grids (dit_xl2 / unet_sd15 style) must realize
        # tile skips from the spatial-only default template
        for grid in ((1, 16, 16), (1, 32, 32)):
            spec = patterns.default_template(grid)
            assert spec.family == "spatial_local"
            skip = patterns.template_skip_rate(spec, grid, (32, 32))
            assert skip > 0.0  # dense's is identically 0


class TestArtifact:
    def test_round_trip_preserves_version(self, tmp_path):
        art = _toy_artifact()
        path = str(tmp_path / "patterns.json")
        patterns.save_pattern_artifact(art, path)
        back = patterns.load_pattern_artifact(path)
        assert back is not None
        assert back.version == art.version
        assert back.heads == art.heads
        assert back.grid == art.grid

    def test_assignment_and_keep_routing(self):
        art = _toy_artifact()
        assert art.assignment(0, 0).static
        assert art.assignment(0, 2) is None  # dynamic -> no static spec
        keep = art.keep_for(art.grid, 3)
        n = int(np.prod(art.grid))
        assert keep.shape == (3, n, n)
        assert keep[2].all()  # dynamic head: unmasked
        assert not keep[0].all()
        assert tuple(art.branches(3)) == ("spatial", "spatial", "dynamic")

    def test_corrupt_bytes_warn_and_none(self, tmp_path):
        path = tmp_path / "patterns.json"
        path.write_bytes(b"\x00{garbage not json")
        with pytest.warns(RuntimeWarning, match="pattern artifact"):
            assert patterns.load_pattern_artifact(str(path)) is None

    def test_schema_mismatch_warns_and_none(self, tmp_path):
        path = tmp_path / "patterns.json"
        path.write_text(json.dumps({"schema": "repro-pattern/999",
                                    "grid": [2, 4, 4], "heads": {}}))
        with pytest.warns(RuntimeWarning, match="pattern artifact"):
            assert patterns.load_pattern_artifact(str(path)) is None

    def test_missing_file_is_quietly_none(self, tmp_path):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert patterns.load_pattern_artifact(
                str(tmp_path / "absent.json")) is None

    def test_install_artifact_raises_on_corrupt(self, tmp_path):
        path = tmp_path / "bad.json"
        path.write_text("{]")
        with pytest.raises(ValueError, match="no usable pattern artifact"), \
                warnings.catch_warnings():
            warnings.simplefilter("ignore")
            patterns.install_artifact(str(path))

    def test_env_var_paths_artifact(self, tmp_path, monkeypatch):
        path = str(tmp_path / "env_patterns.json")
        monkeypatch.setenv("REPRO_PATTERN_ARTIFACT", path)
        assert patterns.pattern_artifact_path() == path
        patterns.save_pattern_artifact(_toy_artifact())
        assert json.load(open(path))["schema"] == patterns.PATTERN_SCHEMA


class TestAutotuneCacheHardening:
    """Satellite: the autotune disk cache warns and regenerates on
    garbage bytes or a version-mismatched schema instead of crashing."""

    def _reset(self):
        dispatch.clear_plan_cache()

    def test_garbage_bytes_warn_and_empty(self, tmp_path, monkeypatch):
        path = tmp_path / "autotune.json"
        path.write_bytes(b"\x93\xffnot json at all")
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
        self._reset()
        try:
            with pytest.warns(RuntimeWarning, match="corrupt"):
                assert dispatch._load_disk_cache() == {}
        finally:
            self._reset()

    def test_schema_mismatch_warns_and_empty(self, tmp_path, monkeypatch):
        path = tmp_path / "autotune.json"
        path.write_text(json.dumps({"__schema__": "repro-autotune/999",
                                    "cpu:pallas:n64:d8:dv8":
                                        {"block_q": 16, "block_k": 16}}))
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
        self._reset()
        try:
            with pytest.warns(RuntimeWarning, match="schema"):
                assert dispatch._load_disk_cache() == {}
        finally:
            self._reset()

    def test_malformed_entries_dropped(self, tmp_path, monkeypatch):
        path = tmp_path / "autotune.json"
        good = {"block_q": 16, "block_k": 16, "us": 1.0}
        path.write_text(json.dumps({"__schema__": dispatch._AUTOTUNE_SCHEMA,
                                    "k_good": good, "k_bad": {"什么": 1},
                                    "k_str": "nope"}))
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
        self._reset()
        try:
            with pytest.warns(RuntimeWarning, match="malformed"):
                cache = dispatch._load_disk_cache()
            assert cache == {"k_good": good}
        finally:
            self._reset()

    def test_regenerates_with_schema_marker(self, tmp_path, monkeypatch):
        path = tmp_path / "autotune.json"
        path.write_bytes(b"truncated{")
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
        self._reset()
        try:
            q, k, v = _qkv(0, (1, 1, 64, 8))
            with pytest.warns(RuntimeWarning, match="corrupt"):
                dispatch.autotune_attention(
                    q, k, v, candidates=((16, 16), (32, 32)), repeats=1)
            disk = json.load(open(path))
            assert disk["__schema__"] == dispatch._AUTOTUNE_SCHEMA
            assert any(k != "__schema__" for k in disk)
        finally:
            self._reset()


class TestStaticPolicy:
    GRID = (4, 8, 8)
    N = 256

    def test_registered(self):
        assert {"static", "rainfusion"} <= set(list_policies())
        assert getattr(get_policy("static"), "plan_once", False)

    def test_dispatch_matches_manual_sparse_bitwise(self):
        """Satellite: static-policy dispatch is bitwise identical to the
        same constant block map fed manually through the sparse
        backend."""
        q, k, v = _qkv(3, (1, 2, self.N, 16))
        cfg = RippleConfig(enabled=True, policy="static")
        dispatch.clear_plan_cache()
        try:
            with patterns.use_artifact(None):
                plan = dispatch.resolve_plan(q.shape, v.shape, cfg,
                                             backend="sparse",
                                             grid=self.GRID)
                out = attention_dispatch(q, k, v, grid=self.GRID, cfg=cfg,
                                         step=0, total_steps=2,
                                         backend="sparse")
                keep = patterns.pattern_keep(None, self.GRID, 2)
            bm = patterns.block_map_np(keep, plan.block_q, plan.block_k)
            bias = None
            if (bm == PARTIAL).any():
                bias = jnp.where(jnp.asarray(keep), 0.0,
                                 -jnp.inf).astype(jnp.float32)
            manual = sparse_attention_pallas(
                q, k, v, bias=bias, block_map=jnp.asarray(bm),
                block_q=plan.block_q, block_k=plan.block_k)
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(manual))
        finally:
            dispatch.clear_plan_cache()

    def test_plan_once_single_refresh_and_stable_outputs(self):
        """plan_once: one refresh at step 0, hits ever after — even at a
        cadence that would re-decide — and bitwise-stable outputs."""
        steps = 6
        q, k, v = _qkv(5, (1, 2, self.N, 16))
        cfg = RippleConfig(enabled=True, policy="static", reuse_every=2)
        assert supports_cache(cfg)
        dispatch.clear_plan_cache()
        try:
            with patterns.use_artifact(None):
                @jax.jit
                def loop(q, k, v):
                    init = initial_state(q.shape, grid=self.GRID, cfg=cfg)

                    def body(carry, si):
                        out, carry = attention_dispatch(
                            q, k, v, grid=self.GRID, cfg=cfg, step=si,
                            total_steps=steps, cached_decision=carry)
                        return carry, out

                    return jax.lax.scan(body, init, jnp.arange(steps))

                final, outs = loop(q, k, v)
            # counters are per (batch, head): exactly one refresh (step
            # 0) and steps-1 hits for every head, despite reuse_every=2
            refreshes = np.asarray(final.refreshes)
            hits = np.asarray(final.hits)
            np.testing.assert_array_equal(
                refreshes, np.ones_like(refreshes))
            np.testing.assert_array_equal(
                hits, np.full_like(hits, steps - 1))
            outs = np.asarray(outs)
            for i in range(1, steps):
                np.testing.assert_array_equal(outs[0], outs[i])
        finally:
            dispatch.clear_plan_cache()

    def test_artifact_swap_changes_plan_token(self):
        pol = get_policy("static")
        art = _toy_artifact()
        with patterns.use_artifact(art):
            assert pol.plan_token(None) == art.version
        with patterns.use_artifact(None):
            assert pol.plan_token(None) is None

    def test_engine_bucket_key_carries_pattern_token(self):
        from repro.serving.engine import _pattern_token

        art = _toy_artifact()
        with patterns.use_artifact(art):
            assert _pattern_token("static") == art.version
            assert _pattern_token("dense") is None
            assert _pattern_token("unregistered") is None
        with patterns.use_artifact(None):
            assert _pattern_token("static") is None

    def test_savings_and_skip_are_structural(self):
        q, k, v = _qkv(7, (1, 2, self.N, 16))
        cfg = RippleConfig(enabled=True, policy="static")
        dispatch.clear_plan_cache()
        try:
            with patterns.use_artifact(None):
                out, stats = attention_dispatch(
                    q, k, v, grid=self.GRID, cfg=cfg, step=0,
                    total_steps=2, backend="sparse", with_stats=True)
            assert float(stats.savings) > 0.0
            assert float(stats.structural_savings) > 0.0
            assert float(stats.q_snap_frac) == 0.0  # no snapping, ever
        finally:
            dispatch.clear_plan_cache()


class TestRainFusion:
    GRID = (4, 8, 8)
    N = 256

    def test_tri_branch_decision(self):
        """Static heads get the constant mask + identity snap sources;
        the dynamic head keeps ripple's snap path."""
        art = _toy_artifact()
        pol = get_policy("rainfusion")
        q, k, _ = _qkv(11, (1, 3, self.N, 16))
        cfg = RippleConfig(enabled=True, theta_min=0.2, theta_max=0.5,
                           i_min=1, i_max=4, policy="rainfusion")
        thetas = pol.thetas_for(cfg, jnp.asarray(2), 6)
        with patterns.use_artifact(art):
            dec = pol.decide(q, k, grid=self.GRID, cfg=cfg, thetas=thetas,
                             block_shape=(32, 32))
        assert dec.bias is not None
        assert dec.block_map is not None
        assert float(sparse_block_stats(dec.block_map)) > 0.0
        # static heads' operands are untouched by snapping
        np.testing.assert_array_equal(np.asarray(dec.q[:, 0]),
                                      np.asarray(q[:, 0]))
        np.testing.assert_array_equal(np.asarray(dec.q[:, 1]),
                                      np.asarray(q[:, 1]))
        if dec.q_mask is not None:
            assert not bool(np.asarray(dec.q_mask)[:, 0].any())
            assert not bool(np.asarray(dec.q_mask)[:, 1].any())

    def test_no_artifact_degrades_to_ripple(self):
        q, k, v = _qkv(13, (1, 2, self.N, 16))
        cfg_rf = RippleConfig(enabled=True, theta_min=0.2, theta_max=0.5,
                              i_min=1, i_max=4, policy="rainfusion")
        cfg_rp = dataclasses.replace(cfg_rf, policy="ripple")
        dispatch.clear_plan_cache()
        try:
            with patterns.use_artifact(None):
                out_rf = attention_dispatch(q, k, v, grid=self.GRID,
                                            cfg=cfg_rf, step=2,
                                            total_steps=6,
                                            backend="reference")
            out_rp = attention_dispatch(q, k, v, grid=self.GRID,
                                        cfg=cfg_rp, step=2, total_steps=6,
                                        backend="reference")
            np.testing.assert_allclose(np.asarray(out_rf),
                                       np.asarray(out_rp), atol=1e-6)
        finally:
            dispatch.clear_plan_cache()

    def test_sweepable_end_to_end(self):
        """`--policy rainfusion` path: plain dispatch resolves a plan
        and runs with stats under the registered policy name."""
        q, k, v = _qkv(17, (1, 3, self.N, 16))
        cfg = RippleConfig(enabled=True, policy="rainfusion")
        dispatch.clear_plan_cache()
        try:
            with patterns.use_artifact(_toy_artifact()):
                out, stats = attention_dispatch(
                    q, k, v, grid=self.GRID, cfg=cfg, step=0,
                    total_steps=2, with_stats=True)
            assert out.shape == q.shape
            assert float(stats.savings) > 0.0
        finally:
            dispatch.clear_plan_cache()


class TestSearchClassification:
    def test_tri_branch_classification_smoke(self):
        """Temporally-correlated heads classify static/temporal-ish,
        unstructured heads stay dynamic (dense)."""
        from repro.launch.pattern_search import calibration_traffic

        grid = (4, 8, 8)
        samples = calibration_traffic(
            grid=grid, layers=1, heads=3, steps=2, prompts=1, d=16,
            characters=("temporal", "spatial", "dynamic"))
        art = patterns.search_patterns(samples, grid,
                                       block_shape=(32, 32),
                                       tolerance_db=20.0)
        a_t = art.heads[(0, 0)]  # temporal character
        a_s = art.heads[(0, 1)]  # spatial character
        a_d = art.heads[(0, 2)]  # dynamic character
        assert a_t.static and a_t.spec.family != "dense"
        assert a_s.static and a_s.spec.family != "dense"
        assert not a_d.static and a_d.spec.family == "dense"
        assert 0.0 < art.static_fraction() < 1.0

    def test_spatial_only_search_on_image_grid(self):
        """T=1 grid: the bank is spatial-only and a spatial head's
        winner realizes tile skips (beats dense)."""
        from repro.launch.pattern_search import calibration_traffic

        grid = (1, 16, 16)
        assert all(s.family in ("dense", "spatial_local", "global_sink")
                   for s in patterns.default_bank(grid))
        samples = calibration_traffic(grid=grid, layers=1, heads=1,
                                      steps=2, prompts=1, d=16,
                                      characters=("spatial",))
        art = patterns.search_patterns(samples, grid,
                                       block_shape=(32, 32),
                                       tolerance_db=20.0)
        a = art.heads[(0, 0)]
        assert a.static
        assert a.skip_rate > 0.0


class TestStaticOnRing:
    def test_static_matches_single_device_and_elides(self):
        """Constant maps on the 2-shard ring: same output, and the
        off-diagonal all-SKIP hop is elided shard-locally."""
        from conftest import require_devices
        from repro.core import decision_cache as dc
        from repro.launch.mesh import parse_mesh_spec

        require_devices(2)
        grid = (4, 8, 8)
        n = 256
        q, k, v = _qkv(23, (1, 2, n, 16))
        cfg = RippleConfig(enabled=True, policy="static", reuse_every=2)
        dispatch.clear_plan_cache()
        try:
            with patterns.use_artifact(None):
                ref = attention_dispatch(q, k, v, grid=grid, cfg=cfg,
                                         step=0, total_steps=2,
                                         backend="sparse")
                mesh = parse_mesh_spec("1x1x2")
                with dispatch.dispatch_mesh(mesh):
                    state = dc.initial_state(q.shape, grid=grid, cfg=cfg,
                                             policy="static",
                                             backend="sparse")
                    out = None
                    for s in range(2):
                        out, state = attention_dispatch(
                            q, k, v, grid=grid, cfg=cfg,
                            step=jnp.asarray(s), total_steps=2,
                            backend="sparse", cached_decision=state,
                            return_decision=True)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=3e-5)
            assert state.elided is not None
            assert int(np.asarray(state.elided).sum()) > 0
            # plan-once on the ring too: one refresh per shard
            assert int(np.asarray(state.refreshes).sum()) == \
                len(np.asarray(state.refreshes).ravel())
        finally:
            dispatch.clear_plan_cache()
