"""Multi-device equivalence + collective-schedule tests.

These run in subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8
(the main test process must keep seeing 1 device, per the dry-run rules).

  * sharded-vs-single numerical equivalence for the MoE block and a full
    train step (the sharding rules change nothing but placement);
  * compiled-HLO all-reduce counts for PT vs dense TP — the paper's
    2L -> L/D sync-point claim verified on the real compiled program,
    for both the training forward and the serving decode step.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.core.track import (dense_tp_sync_points, pt_sync_points,
                              sync_reduction)

ROOT = Path(__file__).resolve().parent.parent

slow = pytest.mark.slow                # subprocess compiles take minutes


def _run(code: str) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"       # virtual CPU devices; never a chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    return json.loads(line)


def test_sync_accounting_closed_form():
    """The paper's §2.2 arithmetic: Megatron TP pays 2 all-reduces per
    layer, PT pays one per D-layer track block — a 2D reduction."""
    assert dense_tp_sync_points(32) == 64
    assert pt_sync_points(32, 8) == 4
    assert sync_reduction(32, 8) == 16           # '16x fewer at D=8'
    assert sync_reduction(48, 4) == 8
    # ragged depth: a final partial block still fuses once
    assert pt_sync_points(10, 4) == 3
    assert pt_sync_points(10, 4, fuse_final=False) == 2


@slow
def test_moe_sharded_equals_single():
    res = _run(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.common.compat import make_mesh
        from repro.configs import reduced_config
        from repro.models import moe as moe_lib
        from repro.runtime.parallel import NO_PARALLEL, Parallelism, TRAIN_RULES

        import dataclasses
        cfg = reduced_config('deepseek-v3-671b')
        # ample capacity: drops are order-dependent and would legitimately
        # differ between the single and sharded dispatch orders
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=64.0))
        params = moe_lib.moe_init(jax.random.PRNGKey(0), cfg, cfg.d_model)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model))
        y0, aux0 = moe_lib.moe_apply(params, x, cfg=cfg, par=NO_PARALLEL)

        mesh = make_mesh((2, 4), ('data', 'model'))
        par = Parallelism(mesh=mesh, rules=dict(TRAIN_RULES))
        y1, aux1 = jax.jit(lambda p, x: moe_lib.moe_apply(
            p, x, cfg=cfg, par=par))(params, x)
        err = float(jnp.max(jnp.abs(y1 - y0)))
        print(json.dumps({'err': err, 'aux0': float(aux0),
                          'aux1': float(aux1)}))
    """))
    assert res["err"] < 2e-4, res


@slow
def test_train_step_sharded_equals_single():
    res = _run(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        from repro.common.compat import make_mesh
        from repro.configs import reduced_config
        from repro.launch import steps as S
        from repro.runtime import sharding as sh
        from repro.data.pipeline import DataConfig, sample_batch

        cfg = reduced_config('tinyllama-1.1b')
        fns = S.model_fns(cfg)
        params = fns['init'](jax.random.PRNGKey(0), cfg)
        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                          global_batch=8)
        batch = {k: jnp.asarray(v) for k, v in sample_batch(dcfg, 0).items()}

        # single device
        par0 = S.build_parallelism(cfg, 'train', None)
        step0, init0, _ = S.make_train_step(cfg, par0, microbatches=2)
        p0, o0, m0 = jax.jit(step0)(params, init0(params), batch)

        # 2x4 mesh
        mesh = make_mesh((2, 4), ('data', 'model'))
        par1 = S.build_parallelism(cfg, 'train', mesh)
        step1, init1, _ = S.make_train_step(cfg, par1, microbatches=2)
        psh = sh.param_shardings(params, cfg, par1)
        osh = sh.opt_state_shardings(init1(params), cfg, par1)
        p1, o1, m1 = jax.jit(step1, in_shardings=(psh, osh, None),
                             out_shardings=(psh, osh, None))(
            params, init1(params), batch)
        dl = abs(float(m0['loss']) - float(m1['loss']))
        dp = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                       - b.astype(jnp.float32))))
                 for a, b in zip(jax.tree_util.tree_leaves(p0),
                                 jax.tree_util.tree_leaves(p1)))
        print(json.dumps({'dloss': dl, 'dparams': dp}))
    """))
    assert res["dloss"] < 1e-4, res
    assert res["dparams"] < 5e-3, res


@slow
def test_pt_sync_points_in_compiled_hlo():
    """The paper's claim, verified structurally: dense Megatron-TP fires
    2 all-reduces per layer; PT fires L/D cross-track all-reduces."""
    res = _run(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        from repro.common.compat import make_mesh
        from repro.configs import pt_paper
        from repro.core.track import pt_ify, pt_sync_points
        from repro.launch import steps as S
        from repro.roofline import hlo as H
        from repro.runtime import sharding as sh

        def collectives(cfg, mesh, par):
            fns = S.model_fns(cfg)
            ps = jax.eval_shape(lambda: fns['init'](jax.random.PRNGKey(0), cfg))
            psh = sh.param_shardings(ps, cfg, par)
            B, Sq = 8, 32
            batch = {'inputs': jax.ShapeDtypeStruct((B, Sq), jnp.int32)}
            bsh = sh.batch_shardings(batch, cfg, par)
            def fwd(p, b):
                out = fns['forward'](p, b, cfg, par, mode='train')
                return out[0].sum()
            comp = jax.jit(fwd, in_shardings=(psh, bsh)).lower(ps, batch).compile()
            res = H.analyze_text(comp.as_text(), 8)
            return res.get('all-reduce_count', 0)

        L, D = 8, 4
        dense = pt_paper.reduced_dense().replace(n_layers=L, remat=False)
        mesh_d = make_mesh((1, 8), ('data', 'model'))
        par_d = S.build_parallelism(dense, 'train', mesh_d)
        ar_dense = collectives(dense, mesh_d, par_d)

        pt = pt_ify(dense, 4, D, width_mult=16).replace(remat=False)
        mesh_t = make_mesh((2, 4), ('data', 'track'))
        par_t = S.build_parallelism(pt, 'train', mesh_t)
        ar_pt = collectives(pt, mesh_t, par_t)
        print(json.dumps({'dense': int(ar_dense), 'pt': int(ar_pt),
                          'expected_pt': pt_sync_points(L, D)}))
    """))
    # dense: >= 2 ARs per layer (activation syncs); PT: exactly L/D
    # cross-track fusions + 3 input/output-boundary syncs (embedding
    # gather, logits, loss reduction) that the paper also acknowledges
    assert res["pt"] <= res["expected_pt"] + 3, res
    assert res["dense"] >= 2 * 8, res
    assert res["dense"] / max(res["pt"], 1) >= 3, res


@slow
def test_pt_paged_decode_one_allreduce_per_track_block():
    """The paged cache must not change the sync structure: pt_decode_step
    over block pools + a block table still compiles to exactly ONE
    cross-track all-reduce per track-block scan iteration — the paged
    scatter/gather stays track-local (the pool's track dim shards with
    the params) and adds no collectives."""
    res = _run(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        from repro.common.compat import make_mesh
        from repro.common.paged import wrap_paged
        from repro.configs import pt_paper
        from repro.launch import steps as S
        from repro.roofline import hlo as H
        from repro.runtime import sharding as sh
        from repro.serving.cache import PagedKVCache

        cfg = pt_paper.reduced_pt(2).replace(remat=False)  # 8 layers, D=2
        n_tracks = cfg.pt.n_tracks
        mesh = make_mesh((2, n_tracks), ('data', 'track'))
        par = S.build_parallelism(cfg, 'decode', mesh)
        fns = S.model_fns(cfg)
        ps = jax.eval_shape(lambda: fns['init'](jax.random.PRNGKey(0), cfg))
        psh = sh.param_shardings(ps, cfg, par)
        B, SL = 8, 32
        kv = PagedKVCache(fns['init_cache'], cfg, max_slots=B,
                          max_seq_len=SL, block_size=8)
        for s in range(B):
            kv.allocate(s, 16)
        cache = jax.eval_shape(lambda: wrap_paged(kv.data, kv.pageable))
        tok = jax.ShapeDtypeStruct((B,), jnp.int32)
        pos = jax.ShapeDtypeStruct((B,), jnp.int32)
        tbl = jax.ShapeDtypeStruct(kv.table_np.shape, jnp.int32)

        def step(p, c, t, q, tb):
            return fns['decode'](p, c, t, q, cfg, par, block_table=tb)

        txt = jax.jit(step, in_shardings=(psh, None, None, None, None)) \\
            .lower(ps, cache, tok, pos, tbl).compile().as_text()

        loops = H.loop_all_reduces(txt, 8)
        print(json.dumps({'per_body': sorted(l['all_reduces'] for l in loops),
                          'group_sizes': [g for l in loops
                                          for g in l['group_sizes']],
                          'n_tracks': n_tracks}))
    """))
    assert res["per_body"].count(1) == 1 and max(res["per_body"]) == 1, res
    assert res["group_sizes"] == [res["n_tracks"]], res


@slow
def test_pt_decode_one_allreduce_per_track_block():
    """The serving-side sync claim, verified structurally: the compiled
    pt_decode_step scans one track block per while iteration, and that
    while body contains EXACTLY ONE cross-track all-reduce (the fusion
    mean) — grouped over the n_tracks mesh axis."""
    res = _run(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        from repro.common.compat import make_mesh
        from repro.configs import pt_paper
        from repro.launch import steps as S
        from repro.roofline import hlo as H
        from repro.runtime import sharding as sh

        cfg = pt_paper.reduced_pt(2).replace(remat=False)  # 8 layers, D=2
        n_tracks = cfg.pt.n_tracks
        mesh = make_mesh((2, n_tracks), ('data', 'track'))
        par = S.build_parallelism(cfg, 'decode', mesh)
        fns = S.model_fns(cfg)
        ps = jax.eval_shape(lambda: fns['init'](jax.random.PRNGKey(0), cfg))
        psh = sh.param_shardings(ps, cfg, par)
        B, SL = 8, 32
        cache = jax.eval_shape(lambda: fns['init_cache'](cfg, B, SL))
        tok = jax.ShapeDtypeStruct((B,), jnp.int32)
        pos = jax.ShapeDtypeStruct((B,), jnp.int32)

        def step(p, c, t, q):
            return fns['decode'](p, c, t, q, cfg, par)

        txt = jax.jit(step, in_shardings=(psh, None, None, None)) \\
            .lower(ps, cache, tok, pos).compile().as_text()

        loops = H.loop_all_reduces(txt, 8)
        print(json.dumps({'per_body': sorted(l['all_reduces'] for l in loops),
                          'group_sizes': [g for l in loops
                                          for g in l['group_sizes']],
                          'n_tracks': n_tracks}))
    """))
    # exactly one loop body carries a collective — the track-block scan —
    # and it carries exactly ONE all-reduce (auxiliary gather/scatter
    # loops XLA emits on CPU carry none)
    assert res["per_body"].count(1) == 1 and max(res["per_body"]) == 1, res
    # ... and it reduces across the track axis (group size = n_tracks)
    assert res["group_sizes"] == [res["n_tracks"]], res


@slow
def test_pt_draft_step_zero_cross_track_allreduces():
    """The drafter's whole point: slicing d of n tracks and stripping the
    'track' mesh axis makes the compiled draft step carry ZERO all-
    reduces — drafting K tokens costs no communication at all (the
    fusion mean over the d-track stack is local compute on every
    device)."""
    res = _run(textwrap.dedent("""
        import json, re
        import jax, jax.numpy as jnp
        from repro.common.compat import make_mesh
        from repro.configs import pt_paper
        from repro.core import track as pt_lib
        from repro.launch import steps as S

        cfg = pt_paper.reduced_pt(2).replace(remat=False)  # 8 layers, D=2
        n_tracks = cfg.pt.n_tracks
        mesh = make_mesh((2, n_tracks), ('data', 'track'))
        par = S.build_parallelism(cfg, 'decode', mesh)
        draft, draft_cfg = S.make_draft_step(cfg, par, draft_tracks=2)

        ps = jax.eval_shape(lambda: pt_lib.pt_draft_params(
            pt_lib.init_pt(jax.random.PRNGKey(0), cfg), cfg, 2))
        B, SL = 8, 32
        cache = jax.eval_shape(
            lambda: pt_lib.pt_init_cache(draft_cfg, B, SL))
        tok = jax.ShapeDtypeStruct((B,), jnp.int32)
        pos = jax.ShapeDtypeStruct((B,), jnp.int32)

        txt = jax.jit(draft).lower(ps, cache, tok, pos).compile().as_text()
        ar = re.compile(r'=\\s*\\S+\\s+all-reduce(?:-start)?\\(')
        n_ar = sum(1 for l in txt.splitlines() if ar.search(l))
        print(json.dumps({'all_reduces': n_ar}))
    """))
    assert res["all_reduces"] == 0, res


@slow
def test_pt_verify_step_one_allreduce_per_track_block():
    """The K+1-token verify forward keeps the decode sync structure: the
    compiled chunk/verify step over the paged cache carries EXACTLY ONE
    cross-track all-reduce per track-block scan iteration — scoring a
    whole draft costs the same L/D sync points as emitting one token."""
    res = _run(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        from repro.common.compat import make_mesh
        from repro.common.paged import wrap_paged
        from repro.configs import pt_paper
        from repro.launch import steps as S
        from repro.roofline import hlo as H
        from repro.runtime import sharding as sh
        from repro.serving.cache import PagedKVCache

        cfg = pt_paper.reduced_pt(2).replace(remat=False)  # 8 layers, D=2
        n_tracks = cfg.pt.n_tracks
        mesh = make_mesh((2, n_tracks), ('data', 'track'))
        par = S.build_parallelism(cfg, 'decode', mesh)
        fns = S.model_fns(cfg)
        ps = jax.eval_shape(lambda: fns['init'](jax.random.PRNGKey(0), cfg))
        psh = sh.param_shardings(ps, cfg, par)
        B, SL, K = 8, 32, 3
        kv = PagedKVCache(fns['init_cache'], cfg, max_slots=B,
                          max_seq_len=SL, block_size=8)
        for s in range(B):
            kv.allocate(s, 16)
        cache = jax.eval_shape(lambda: wrap_paged(kv.data, kv.pageable))
        tok = jax.ShapeDtypeStruct((B, K + 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((B,), jnp.int32)
        tbl = jax.ShapeDtypeStruct(kv.table_np.shape, jnp.int32)

        verify = S.make_verify_step(cfg, par)

        txt = jax.jit(verify, in_shardings=(psh, None, None, None, None)) \\
            .lower(ps, cache, tok, pos, tbl).compile().as_text()

        loops = H.loop_all_reduces(txt, 8)
        print(json.dumps({'per_body': sorted(l['all_reduces'] for l in loops),
                          'group_sizes': [g for l in loops
                                          for g in l['group_sizes']],
                          'n_tracks': n_tracks}))
    """))
    assert res["per_body"].count(1) == 1 and max(res["per_body"]) == 1, res
    assert res["group_sizes"] == [res["n_tracks"]], res


@slow
def test_pt_quantized_paged_decode_one_allreduce_per_track_block():
    """Quantization must not change the sync structure either: int8
    weights (payload + scale sharded like the fp leaf) and an int8 KV
    pool (dequant is an elementwise multiply against the gathered scale
    pool, local to every track) still compile to exactly ONE cross-track
    all-reduce per track-block scan iteration."""
    res = _run(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        from repro.common.compat import make_mesh
        from repro.common.paged import wrap_paged
        from repro.common.quant import quantize_params
        from repro.configs import pt_paper
        from repro.launch import steps as S
        from repro.roofline import hlo as H
        from repro.runtime import sharding as sh
        from repro.serving.cache import PagedKVCache

        cfg = pt_paper.reduced_pt(2).replace(remat=False)  # 8 layers, D=2
        n_tracks = cfg.pt.n_tracks
        mesh = make_mesh((2, n_tracks), ('data', 'track'))
        par = S.build_parallelism(cfg, 'decode', mesh)
        fns = S.model_fns(cfg)
        ps = jax.eval_shape(lambda: quantize_params(
            fns['init'](jax.random.PRNGKey(0), cfg))[0])
        psh = sh.param_shardings(ps, cfg, par)
        B, SL = 8, 32
        kv = PagedKVCache(fns['init_cache'], cfg, max_slots=B,
                          max_seq_len=SL, block_size=8, kv_dtype='int8')
        for s in range(B):
            kv.allocate(s, 16)
        cache = jax.eval_shape(
            lambda: wrap_paged(kv.data, kv.pageable, kv.scales))
        tok = jax.ShapeDtypeStruct((B,), jnp.int32)
        pos = jax.ShapeDtypeStruct((B,), jnp.int32)
        tbl = jax.ShapeDtypeStruct(kv.table_np.shape, jnp.int32)

        def step(p, c, t, q, tb):
            return fns['decode'](p, c, t, q, cfg, par, block_table=tb)

        txt = jax.jit(step, in_shardings=(psh, None, None, None, None)) \\
            .lower(ps, cache, tok, pos, tbl).compile().as_text()

        loops = H.loop_all_reduces(txt, 8)
        print(json.dumps({'per_body': sorted(l['all_reduces'] for l in loops),
                          'group_sizes': [g for l in loops
                                          for g in l['group_sizes']],
                          'n_tracks': n_tracks}))
    """))
    assert res["per_body"].count(1) == 1 and max(res["per_body"]) == 1, res
    assert res["group_sizes"] == [res["n_tracks"]], res


@slow
def test_pt_quantized_draft_step_zero_cross_track_allreduces():
    """Drafting stays communication-free with int8 weights: the draft
    params are sliced from the full tracks FIRST and quantized after
    (payload and scale slice together would de-align otherwise), and the
    compiled draft step still carries ZERO all-reduces."""
    res = _run(textwrap.dedent("""
        import json, re
        import jax, jax.numpy as jnp
        from repro.common.compat import make_mesh
        from repro.common.quant import quantize_params
        from repro.configs import pt_paper
        from repro.core import track as pt_lib
        from repro.launch import steps as S

        cfg = pt_paper.reduced_pt(2).replace(remat=False)  # 8 layers, D=2
        n_tracks = cfg.pt.n_tracks
        mesh = make_mesh((2, n_tracks), ('data', 'track'))
        par = S.build_parallelism(cfg, 'decode', mesh)
        draft, draft_cfg = S.make_draft_step(cfg, par, draft_tracks=2)

        ps = jax.eval_shape(lambda: quantize_params(pt_lib.pt_draft_params(
            pt_lib.init_pt(jax.random.PRNGKey(0), cfg), cfg, 2))[0])
        B, SL = 8, 32
        cache = jax.eval_shape(
            lambda: pt_lib.pt_init_cache(draft_cfg, B, SL))
        tok = jax.ShapeDtypeStruct((B,), jnp.int32)
        pos = jax.ShapeDtypeStruct((B,), jnp.int32)

        txt = jax.jit(draft).lower(ps, cache, tok, pos).compile().as_text()
        ar = re.compile(r'=\\s*\\S+\\s+all-reduce(?:-start)?\\(')
        n_ar = sum(1 for l in txt.splitlines() if ar.search(l))
        print(json.dumps({'all_reduces': n_ar}))
    """))
    assert res["all_reduces"] == 0, res
