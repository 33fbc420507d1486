"""The system under test, as the benchmark drives it: the program's model
configuration built from a configuration file, the serving engine with
the file's settings, and a warm-up of every program shape the window can
reach.  This is the only module of the benchmark that imports the
program (``src/repro``)."""
from __future__ import annotations

import sys
from typing import Any, Dict, List

import numpy as np

from bench.spec import ROOT

sys.path.insert(0, str(ROOT / "src"))

import dataclasses  # noqa: E402

import jax  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch import steps as steps_lib  # noqa: E402
from repro.serving.engine import Engine  # noqa: E402
from repro.serving.sampler import SampleParams  # noqa: E402

# configuration-file keys of the ``model`` block -> program config fields
MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab_size", "rope_theta", "norm_eps", "dtype",
              "tie_embeddings")
PT_KEYS = ("n_tracks", "block_depth")


def program_config(conf: Dict[str, Any]):
    """The program's config for a configuration file: the registry entry
    it names, with the file's sizes put in.  A size that differs from
    the registry entry must be listed in the file's ``reduced``."""
    m = conf["model"]
    cfg = get_config(conf["program_arch"])
    over = {k: m[k] for k in MODEL_KEYS if getattr(cfg, k) != m[k]}
    pt_over = {}
    if cfg.pt is None:
        if m["n_tracks"] != 1:
            raise SystemExit(f"{conf['name']}: {conf['program_arch']} has "
                             "no tracks")
    else:
        pt_over = {k: m[k] for k in PT_KEYS if getattr(cfg.pt, k) != m[k]}
        if cfg.pt.fusion_op != m["fusion"] or not cfg.pt.fuse_final:
            raise SystemExit(f"{conf['name']}: track fusion differs")
    unlisted = sorted((set(over) | set(pt_over)) - set(conf["reduced"]))
    if unlisted:
        raise SystemExit(f"{conf['name']}: {unlisted} differ from "
                         f"{conf['program_arch']} but are not in 'reduced'")
    if pt_over:
        over["pt"] = dataclasses.replace(cfg.pt, **pt_over)
    cfg = cfg.replace(**over) if over else cfg
    spec = cfg.spec(cfg.pattern_unit[0])
    layer = conf["layer"]
    got = {"mixer": spec.mixer, "mlp": spec.mlp, "rope": spec.rope,
           "norm": cfg.norm, "window": spec.window,
           "softcap": spec.attn_logit_softcap,
           "final_softcap": cfg.final_logit_softcap,
           "qk_norm": cfg.qk_norm, "post_norm": cfg.post_norm,
           "embedding_multiplier": cfg.embedding_multiplier}
    if got != layer or len(cfg.pattern_unit) != 1 or cfg.pattern_prefix \
            or cfg.pattern_suffix:
        raise SystemExit(f"{conf['name']}: the program's layer {got} is not "
                         f"the file's {layer}")
    return cfg


def param_shapes(cfg):
    fns = steps_lib.model_fns(cfg)
    return jax.eval_shape(lambda k: fns["init"](k, cfg),
                          jax.random.PRNGKey(0))


def build_engine(cfg, params, e: Dict[str, Any]) -> Engine:
    return Engine(cfg, params, max_slots=e["max_slots"],
                  max_seq_len=e["max_seq_len"],
                  max_waiting_prefill_tokens=e["max_waiting_prefill_tokens"],
                  paged=True, block_size=e["block_size"],
                  num_blocks=e["num_blocks"],
                  prefill_chunk=e["prefill_chunk"],
                  prefix_cache=e["prefix_cache"])


GREEDY = SampleParams(temperature=0.0)


def warm_up(eng: Engine) -> List[str]:
    """Run every program the window can reach once: the chunk program for
    each number of prefilling rows (1 to slots) and the decode program.
    Lanes with no blocks write to the pool's trash block, so no request's
    cache is touched.  Returns what was run."""
    r = eng.runner
    if not r.prefill_chunk or r.speculate_k or r.has_dense_leaves:
        raise SystemExit("the benchmark's warm-up covers the chunked, "
                         "all-paged, non-speculative path only")
    C, S = r.prefill_chunk, r.max_slots
    ran = []
    for n in range(1, S + 1):
        r.chunk(np.ones((n, C), np.int32), np.zeros((n,), np.int32),
                list(range(n)), np.full((n,), C - 1, np.int32),
                [0] * n, [0] * n, [GREEDY] * n)
        ran.append(f"chunk[{n}x{C}]")
    z = np.zeros((S,), np.int32)
    r.decode(z, z, np.zeros((S,), bool), np.zeros((S,), np.uint32), z,
             np.zeros((S,), np.float32), z, np.ones((S,), np.float32),
             np.full((S,), -1, np.int32), z)
    ran.append(f"decode[{S}]")
    r.chunk_calls = r.prefill_calls = r.decode_transfers = 0
    return ran
