"""Launch-path smoke: the dry-run driver lowers+compiles representative
cells on a small virtual mesh in a subprocess (keeps this process at 1
device)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow          # subprocess compiles take minutes

ROOT = Path(__file__).resolve().parent.parent


def _run(code: str) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"       # virtual CPU devices; never a chip
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    return json.loads(line)


def test_dryrun_reduced_cells_on_virtual_mesh():
    res = _run(textwrap.dedent("""
        import os
        os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
        import json
        import jax, jax.numpy as jnp
        from repro.common.compat import make_mesh
        from repro.common.types import ShapeSpec
        from repro.configs import reduced_config
        from repro.launch import steps as S
        from repro.runtime import sharding as sh

        mesh = make_mesh((2, 4), ('data', 'model'))
        out = {}
        for arch, kind in (('gemma3-4b', 'train'),
                           ('falcon-mamba-7b', 'decode'),
                           ('deepseek-v3-671b', 'train')):
            cfg = reduced_config(arch)
            shape = ShapeSpec('s', 32, 8, kind)
            par = S.build_parallelism(cfg, kind, mesh)
            ps = S.param_specs(cfg)
            psh = sh.param_shardings(ps, cfg, par)
            if kind == 'train':
                step, opt_init, _ = S.make_train_step(cfg, par,
                                                      microbatches=2)
                os_ = jax.eval_shape(opt_init, ps)
                osh = sh.opt_state_shardings(os_, cfg, par)
                b = S.batch_specs(cfg, shape)
                bsh = sh.batch_shardings(b, cfg, par)
                c = jax.jit(step, in_shardings=(psh, osh, bsh),
                            out_shardings=(psh, osh, None)
                            ).lower(ps, os_, b).compile()
            else:
                parw = S.build_parallelism(cfg, 'train', mesh)
                psh = sh.param_shardings(ps, cfg, parw)
                step = S.make_serve_step(cfg, par)
                d = S.decode_specs(cfg, shape)
                csh = sh.cache_shardings(d['cache'], cfg, par)
                c = jax.jit(step, in_shardings=(psh, csh, None, None)
                            ).lower(ps, d['cache'], d['tokens'],
                                    d['pos']).compile()
            out[arch] = int(c.memory_analysis().temp_size_in_bytes)
        print(json.dumps(out))
    """))
    assert len(res) == 3 and all(v >= 0 for v in res.values()), res
