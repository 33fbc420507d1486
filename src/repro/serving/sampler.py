"""Token samplers: greedy / temperature / top-k / top-p, plus the
speculative-decoding accept step.

Entry points:

  sample(logits, key, params)            — single SampleParams for the whole
      batch, Python-branching on the param values (kept for tests/tools).
  sample_batched(logits, key, t, k, p)   — per-row params as *traced arrays*,
      fully branch-free, one shared key.
  sample_rows(logits, keys, t, k, p)     — same, but with PER-ROW keys
      [B, 2]: each slot's randomness depends only on its own request seed
      and token counter, never on batch composition.
  sample_step(...)                       — fused decode-step epilogue:
      per-slot sampling + done flags, packed [2, B] int32 (ONE transfer).
  accept_step(...)                       — speculative decoding: batched
      rejection sampling over K draft tokens + a bonus token per slot,
      packed [K+2, B] int32 (tokens ‖ emitted-count; still ONE transfer).

``row_keys(seeds, counters, salt)`` derives the per-row keys; distinct
salts separate the draft / accept / resample randomness streams so a
request replays bit-identically regardless of who shares its batch.
``stack_params`` converts a list of SampleParams into the three arrays
the batched samplers consume.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30

# salts for the per-request randomness streams (row_keys)
SALT_SAMPLE = 0        # plain decode / resample / bonus token draws
SALT_ACCEPT = 1        # speculative accept uniforms
SALT_DRAFT = 2         # drafter's own sampling


@dataclasses.dataclass(frozen=True)
class SampleParams:
    temperature: float = 0.0          # 0 => greedy
    top_k: int = 0                    # 0 => no top-k filter
    top_p: float = 1.0                # 1 => no nucleus filter


def stack_params(params: Sequence[SampleParams]
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """[SampleParams] -> (temperature [B] f32, top_k [B] i32, top_p [B] f32)."""
    return (np.asarray([p.temperature for p in params], np.float32),
            np.asarray([p.top_k for p in params], np.int32),
            np.asarray([p.top_p for p in params], np.float32))


def fork_seeds(base_seed: int, n: int) -> list:
    """``n`` distinct deterministic sampling seeds for fork children,
    never colliding with the parent's ``base_seed`` (a child that reused
    it would replay the parent's stream and defeat parallel sampling).
    splitmix-style avalanche over (base_seed, child index)."""
    base = base_seed & 0xFFFFFFFF
    seen = {base}
    out: list = []
    i = 0
    while len(out) < n:
        i += 1
        z = (base + i * 0x9E3779B9) & 0xFFFFFFFF
        z = ((z ^ (z >> 16)) * 0x85EBCA6B) & 0xFFFFFFFF
        z = ((z ^ (z >> 13)) * 0xC2B2AE35) & 0xFFFFFFFF
        z ^= z >> 16
        if z in seen:
            continue
        seen.add(z)
        out.append(z)
    return out


def row_keys(seeds: jax.Array, counters: jax.Array, salt: int) -> jax.Array:
    """Per-row PRNG keys [B, 2] from (request seed, token counter, salt).

    The key depends ONLY on the request's own seed and its position in
    the output stream, so decode (and spec-decode accept/resample) is
    reproducible per request regardless of batch composition."""
    def one(s, c):
        return jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(s), c), salt)

    return jax.vmap(one)(seeds.astype(jnp.uint32),
                         counters.astype(jnp.int32))


def prefill_keys(seeds: jax.Array, counters: jax.Array) -> jax.Array:
    """Keys for the token sampled at the end of a (re)prefill: draw
    ``counters[i]`` of each row's stream — 0 for a fresh prompt, m for a
    request resuming after preemption with m tokens already emitted.
    Because this is the SAME (seed, counter, salt) triple the decode
    step would have used at that point, a preempted request's recompute
    samples the identical continuation: greedy or sampled, the finished
    output is bitwise-equal to an uncontended run."""
    return row_keys(seeds, counters, SALT_SAMPLE)


def sample(logits: jax.Array, key: jax.Array,
           params: SampleParams = SampleParams()) -> jax.Array:
    """logits: [B, V] -> tokens [B] int32."""
    if params.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / params.temperature
    if params.top_k > 0:
        kth = jax.lax.top_k(logits, params.top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, NEG, logits)
    if params.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < params.top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, NEG, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def filter_logits(logits: jax.Array, temperature: jax.Array,
                  top_k: jax.Array, top_p: jax.Array) -> jax.Array:
    """Temperature-scale + per-row top-k / top-p mask.  logits [B, V] with
    params [B] -> filtered scaled logits [B, V] (NEG outside the support).

    All filters are data-dependent `where` masks over a per-row sort, so
    every caller jits once regardless of the parameter mix across slots.
    """
    logits = logits.astype(jnp.float32)
    V = logits.shape[-1]
    t = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / t
    # top-k: per-row k-th largest value as the cutoff (rank-based)
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k_idx = jnp.clip(top_k[:, None] - 1, 0, V - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx, axis=-1)
    scaled = jnp.where((top_k[:, None] > 0) & (scaled < kth), NEG, scaled)
    # top-p over the (already top-k-filtered) distribution
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.clip(jnp.sum(cum < top_p[:, None], axis=-1,
                                  keepdims=True), 0, V - 1)
    cutoff = jnp.take_along_axis(sorted_desc, cutoff_idx, axis=-1)
    return jnp.where((top_p[:, None] < 1.0) & (scaled < cutoff), NEG,
                     scaled)


def sample_batched(logits: jax.Array, key: jax.Array,
                   temperature: jax.Array, top_k: jax.Array,
                   top_p: jax.Array) -> jax.Array:
    """Per-row sampling with traced params, one shared key.
    logits [B,V] -> tokens [B].  temperature [B] f32 (<=0 row => greedy),
    top_k [B] i32 (<=0 => off), top_p [B] f32 (>=1 => off)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = filter_logits(logits, temperature, top_k, top_p)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


@jax.named_scope("sample")
def sample_rows(logits: jax.Array, keys: jax.Array,
                temperature: jax.Array, top_k: jax.Array,
                top_p: jax.Array) -> jax.Array:
    """``sample_batched`` with per-row keys [B, 2] (see ``row_keys``)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = filter_logits(logits, temperature, top_k, top_p)
    sampled = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


@jax.named_scope("sample")
def sample_step(logits: jax.Array, keys: jax.Array, temperature: jax.Array,
                top_k: jax.Array, top_p: jax.Array, active: jax.Array,
                eos: jax.Array, remaining: jax.Array) -> jax.Array:
    """One fused device-side decode-step epilogue: per-slot sampling plus
    done-flag computation, packed as [2, B] int32 = (token, done) — the
    single host transfer of the decode loop.  ``keys`` [B, 2] are per-row
    (request-seeded) keys.

    ``done`` rows are the engine's reclamation signal: the slot is
    released and (in paged mode) its KV blocks go back to the free pool
    the moment the packed array lands on the host, so a finished short
    request frees memory for queued work without waiting for the batch.
    """
    new = sample_rows(logits, keys, temperature, top_k, top_p)
    new = jnp.where(active, new, 0)
    done = active & ((remaining <= 1) | ((eos >= 0) & (new == eos)))
    return jnp.stack([new, done.astype(jnp.int32)])


# ---------------------------------------------------------------------------
# pipelined stepping: device-side carry of the next step's inputs
# ---------------------------------------------------------------------------
#
# When the engine dispatches step N+1 while step N's packed transfer is
# still in flight, the host does not yet know step N's sampled tokens —
# but the DEVICE does: they are row 0 of the packed array.  These helpers
# compute step N+1's inputs from step N's packed result without a host
# round-trip, so consecutive steps chain device-to-device.  ``override``
# marks lanes whose host-side values are authoritative instead (newly
# admitted / forked / re-assigned slots): their inputs come from the
# h_* arrays, carried lanes advance from the packed result.


def advance_decode(packed: jax.Array, tok: jax.Array, pos: jax.Array,
                   counts: jax.Array, remaining: jax.Array,
                   override: jax.Array, h_tok: jax.Array, h_pos: jax.Array,
                   h_counts: jax.Array, h_remaining: jax.Array
                   ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Next plain-decode inputs from the previous step's ``packed``
    [2, B] result: carried lanes feed packed[0] (the sampled token) back
    as the next input token and advance pos/counter by one, remaining by
    minus one — exactly what the host-side emission loop will compute
    once the transfer lands."""
    n_tok = jnp.where(override, h_tok, packed[0])
    n_pos = jnp.where(override, h_pos, pos + 1)
    n_counts = jnp.where(override, h_counts, counts + 1)
    n_rem = jnp.where(override, h_remaining, remaining - 1)
    return n_tok, n_pos, n_counts, n_rem


def advance_spec(packed: jax.Array, tok: jax.Array, pos: jax.Array,
                 counts: jax.Array, override: jax.Array, h_tok: jax.Array,
                 h_pos: jax.Array, h_counts: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Next speculative-step inputs from the previous step's ``packed``
    [K+2, B] result (rows 0..K emitted tokens, row K+1 the per-slot
    emitted count m).  The spec advance is data-dependent — a lane moves
    1..K+1 tokens — so carried lanes take token packed[m-1] (the last
    emitted) and advance pos/counter by m; a lane with m == 0 (inactive
    last step) keeps its previous values."""
    K1 = packed.shape[0] - 1               # K+1 token rows
    m = packed[-1]                         # [B] emitted counts
    idx = jnp.clip(m - 1, 0, K1 - 1)
    last = jnp.take_along_axis(packed[:-1], idx[None, :], axis=0)[0]
    c_tok = jnp.where(m > 0, last, tok)
    n_tok = jnp.where(override, h_tok, c_tok)
    n_pos = jnp.where(override, h_pos, pos + m)
    n_counts = jnp.where(override, h_counts, counts + m)
    return n_tok, n_pos, n_counts


# ---------------------------------------------------------------------------
# speculative decoding: batched accept / resample
# ---------------------------------------------------------------------------

def _filtered_probs(logits: jax.Array, temperature: jax.Array,
                    top_k: jax.Array, top_p: jax.Array) -> jax.Array:
    """Probability vectors of the filtered distribution; greedy rows
    (temp <= 0) are EXACT one-hots at the argmax, so the generic
    accept/resample math reduces to deterministic argmax agreement —
    greedy spec decode is bitwise-identical to greedy plain decode."""
    greedy = jax.nn.one_hot(jnp.argmax(logits, axis=-1), logits.shape[-1],
                            dtype=jnp.float32)
    probs = jax.nn.softmax(filter_logits(logits, temperature, top_k, top_p),
                           axis=-1)
    return jnp.where((temperature <= 0.0)[:, None], greedy, probs)


@jax.named_scope("sample")
def accept_step(target_logits: jax.Array, draft_logits: jax.Array,
                draft_toks: jax.Array, seeds: jax.Array,
                counters: jax.Array, temperature: jax.Array,
                top_k: jax.Array, top_p: jax.Array,
                active: jax.Array) -> jax.Array:
    """Batched speculative accept/resample over K draft tokens per slot.

    target_logits [B, K+1, V]: verify-forward logits (row j scores the
    token at position pos+j+1); draft_logits [B, K, V] and draft_toks
    [B, K]: the drafter's distributions and sampled tokens.  Standard
    rejection sampling per slot under the slot's own filtered
    (temperature/top-k/top-p) distributions:

      accept d_j  with prob min(1, p_j[d_j] / q_j[d_j]);
      on first rejection, emit a token from norm(max(p_j - q_j, 0));
      if all K accepted, emit a bonus token from p_K.

    The emitted-token marginal equals the target distribution exactly for
    ANY drafter — acceptance rate only changes throughput, never the
    distribution.  Greedy rows use one-hot p/q, so acceptance degenerates
    to argmax agreement and every emitted token is the target argmax.

    Returns packed int32 [K+2, B]: rows 0..K the emitted tokens (padded
    with 0), row K+1 the per-slot emitted count m = n_accepted + 1
    (0 for inactive slots) — one host transfer for the whole spec step.
    EOS / remaining-budget truncation happens host-side on the packed
    result, so no extra device round-trip is needed.
    """
    B, K1, V = target_logits.shape
    K = K1 - 1

    def per_pos(probs_fn, logits3):
        n = logits3.shape[1]
        flat = logits3.reshape(B * n, V)
        rep = lambda a: jnp.repeat(a, n, axis=0)
        out = probs_fn(flat, rep(temperature), rep(top_k), rep(top_p))
        return out.reshape(B, n, V)

    p = per_pos(_filtered_probs, target_logits)          # [B, K+1, V]
    q = per_pos(_filtered_probs, draft_logits)           # [B, K, V]

    # accept test per draft position
    p_at = jnp.take_along_axis(p[:, :K], draft_toks[..., None],
                               axis=-1)[..., 0]          # [B, K]
    q_at = jnp.take_along_axis(q, draft_toks[..., None], axis=-1)[..., 0]
    u = jnp.stack(
        [jax.vmap(lambda k: jax.random.uniform(k, ()))(
            row_keys(seeds, counters + j, SALT_ACCEPT))
         for j in range(K)], axis=1)                     # [B, K]
    accept = u < p_at / jnp.maximum(q_at, 1e-30)         # [B, K]
    n_acc = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)

    # residual (or bonus) distribution at the first rejected position;
    # padding q with zeros makes the all-accepted case max(p_K - 0, 0)
    # = p_K — the bonus draw — with no branch.
    q_pad = jnp.concatenate([q, jnp.zeros((B, 1, V), q.dtype)], axis=1)
    p_n = jnp.take_along_axis(p, n_acc[:, None, None], axis=1)[:, 0]
    q_n = jnp.take_along_axis(q_pad, n_acc[:, None, None], axis=1)[:, 0]
    res = jnp.maximum(p_n - q_n, 0.0)
    res_sum = jnp.sum(res, axis=-1, keepdims=True)
    res = jnp.where(res_sum > 0, res / jnp.maximum(res_sum, 1e-30), p_n)
    res_keys = row_keys(seeds, counters + n_acc, SALT_SAMPLE)
    extra = jax.vmap(jax.random.categorical)(
        res_keys, jnp.log(jnp.maximum(res, 1e-38))).astype(jnp.int32)
    # greedy rows: deterministic argmax of the (one-hot) residual — the
    # categorical above would also land there, but keep it exact.
    extra = jnp.where(temperature <= 0.0,
                      jnp.argmax(res, axis=-1).astype(jnp.int32), extra)

    jr = jnp.arange(K1, dtype=jnp.int32)[None]           # [1, K+1]
    d_pad = jnp.concatenate(
        [draft_toks, jnp.zeros((B, 1), jnp.int32)], axis=1)
    toks = jnp.where(jr < n_acc[:, None], d_pad,
                     jnp.where(jr == n_acc[:, None], extra[:, None], 0))
    m = jnp.where(active, n_acc + 1, 0)
    toks = jnp.where(active[:, None], toks, 0)
    return jnp.concatenate([toks.T.astype(jnp.int32), m[None]], axis=0)
