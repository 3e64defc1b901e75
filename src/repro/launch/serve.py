"""Batched serving of a (SPRY-finetuned) model: prefill + decode loop.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --steps 32

CPU-runnable on reduced configs; the full-config sharded path is what
dryrun.py lowers (prefill_32k / decode_32k / long_500k serve_step). The
multi-tenant continuous-batching engine built on these pieces lives in
``repro.launch.serving``.
"""
from __future__ import annotations

import argparse
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SpryConfig, get_config, reduce_config
from repro.models import get_model
from repro.launch.compile_cache import enable_compile_cache
from repro.models.encdec import encode as encdec_encode
from repro.peft import init_peft

# cache donation through the jitted decode step: XLA reuses the multi-GB
# KV-cache buffers in place instead of allocating a fresh copy per token.
# CPU sometimes declines individual buffers — that is fine, not a bug.
warnings.filterwarnings("ignore",
                        message="Some donated buffers were not usable")


def tokenwise_prefill(cfg, model, base, peft, cache, prompt_tokens,
                      decode=None):
    """Reference prompt ingestion: P decode_step calls (exercises the cache
    exactly as production decode does). Kept as the fallback for families
    whose fused prefill cannot reproduce the loop (quantized / too-short
    ring caches) and as the equivalence oracle in tests. ``decode`` reuses
    an already-jitted decode_step (avoids a second compilation of the
    identical function); when absent a NON-donating one is built so callers
    may keep using the cache they passed in."""
    if decode is None:
        decode = jax.jit(
            lambda base, peft, cache, tok, pos: model.decode_step(
                cfg, base, peft, cache, tok, pos))
    P = prompt_tokens.shape[1]
    for p in range(P):
        logits, cache = decode(base, peft, cache, prompt_tokens[:, p:p + 1],
                               jnp.int32(p))
    return logits, cache


def can_fuse_prefill(cfg, model, cache, prompt_len):
    """Whether ``model.prefill`` reproduces the token-by-token decode loop
    for this cache shape (the fused pass must write exactly the rows the
    loop would have)."""
    if model.prefill is None:
        return False
    if not isinstance(cache, dict):
        return False
    if "k" in cache:
        # int8-KV caches: the decode loop attends to QUANTIZED history
        # during ingestion while a fused pass would attend to exact K/V —
        # not equivalent; take the token loop
        if "k_scale" in cache:
            return False
        # a ring cache SHORTER than the prompt makes the decode loop lossy
        # (early keys are overwritten before later prompt tokens attend);
        # fused attention over the full prompt cannot reproduce that unless
        # every layer is sliding-window AND the ring still covers the window
        Sc = cache["k"].shape[2]
        if Sc < prompt_len:
            all_swa = not any(cfg.is_global_layer(i)
                              for i in range(cfg.n_layers))
            if not (all_swa and Sc >= cfg.window):
                return False
        return True
    if "attn_k" in cache:
        # hybrid shared-attention ring: fusible unless the ring is both
        # shorter than the prompt AND narrower than the window (the loop
        # then wraps while still attending full-window — lossy)
        W = cache["attn_k"].shape[2]
        if W < prompt_len and W < cfg.window:
            return False
        return True
    return True   # stateful families (rwkv): prefill threads exact state


def build_serve_fns(cfg, model):
    """Hoisted jitted serve entry points — build ONCE and reuse across
    requests so steady-state serving never re-traces. The decode step
    donates its cache argument (the multi-GB buffers update in place)."""
    decode = jax.jit(
        lambda base, peft, cache, tok, pos: model.decode_step(
            cfg, base, peft, cache, tok, pos),
        donate_argnums=(2,))
    run_prefill = None
    if model.prefill is not None:
        # the prompt cache is carried state exactly like the decode cache:
        # every caller rebinds it (logits, cache = prefill(...)), so the
        # pre-prefill buffers can be reused in place
        run_prefill = jax.jit(
            lambda base, peft, cache, toks: model.prefill(
                cfg, base, peft, cache, toks),
            donate_argnums=(2,))
    return {"decode": decode, "prefill": run_prefill}


def greedy_generate(cfg, base, peft, prompt_tokens, n_steps, cache_len=None,
                    fused_prefill=True, kv_int8=False, fns=None, frames=None):
    """prompt_tokens: (B, P) int32. Returns (B, n_steps) generated ids.

    ``fused_prefill=True`` ingests the prompt with ONE chunked-attention /
    recurrence pass (model.prefill) instead of P decode_step calls — decode
    output is identical (asserted in tests/test_serve_prefill.py);
    ``can_fuse_prefill`` gates the cases the fused pass cannot reproduce.
    ``fns``: reuse entry points from ``build_serve_fns`` (skips re-jitting
    per call). ``frames``: encoder frames for encoder-decoder families —
    encoded once into the cache's memory slot before the decoder runs.
    """
    model = get_model(cfg)
    B, P = prompt_tokens.shape
    if kv_int8 and not model.supports_kv_int8:
        raise ValueError(
            f"family {cfg.family!r} has no int8-KV cache "
            f"(ModelFns.supports_kv_int8 is False)")
    if model.supports_kv_int8:
        cache = model.init_cache(cfg, B, cache_len or (P + n_steps),
                                 kv_int8=kv_int8)
    else:
        cache = model.init_cache(cfg, B, cache_len or (P + n_steps))
    if frames is not None:
        if not (isinstance(cache, dict) and "memory" in cache):
            raise ValueError("frames given but the cache has no memory slot")
        memory = encdec_encode(cfg, base, frames, peft)
        cache = dict(cache, memory=memory.astype(cache["memory"].dtype))
    if fns is None:
        fns = build_serve_fns(cfg, model)
    decode = fns["decode"]

    if fused_prefill and can_fuse_prefill(cfg, model, cache, P):
        logits, cache = fns["prefill"](base, peft, cache, prompt_tokens)
    else:
        logits, cache = tokenwise_prefill(cfg, model, base, peft, cache,
                                          prompt_tokens, decode=decode)
    out = []
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    for s in range(n_steps):
        out.append(tok)
        logits, cache = decode(base, peft, cache, tok, jnp.int32(P + s))
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    return jnp.concatenate(out, axis=1)


def run_engine(cfg, n_requests, prompt_len, steps, max_batch=4,
               cache_capacity=4, telemetry=None, seed=0):
    """Drive the multi-tenant ServingEngine with ``n_requests`` requests on
    distinct synthetic adapters (the CLI/CI smoke path for the engine +
    adapter cache + telemetry stack). Returns (outputs, engine, requests)."""
    from repro.launch.adapter_cache import AdapterCache, SyntheticAdapterStore
    from repro.launch.serving import Request, ServingEngine

    key = jax.random.PRNGKey(seed)
    model = get_model(cfg)
    base = model.init_base(cfg, key)
    store = SyntheticAdapterStore(cfg, SpryConfig(), seed=seed)
    cache = AdapterCache(store, capacity=cache_capacity, telemetry=telemetry)
    engine = ServingEngine(cfg, base, cache, max_batch=max_batch,
                           cache_len=prompt_len + steps,
                           telemetry=telemetry)
    rng = np.random.default_rng(seed)
    reqs = [Request(request_id=f"req-{i}", adapter_id=i % max(1, n_requests),
                    prompt=rng.integers(0, cfg.vocab,
                                        size=prompt_len).astype(np.int32),
                    max_new_tokens=steps)
            for i in range(n_requests)]
    outputs = engine.run(reqs)
    return outputs, engine, reqs


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-1.6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--full-size", action="store_true")
    ap.add_argument("--engine", type=int, default=0, metavar="N",
                    help="serve N multi-tenant requests through the "
                         "continuous-batching ServingEngine instead of the "
                         "single-tenant greedy loop")
    ap.add_argument("--cache-capacity", type=int, default=4,
                    help="resident adapter pages in the AdapterCache "
                         "(engine mode)")
    ap.add_argument("--telemetry", default=None,
                    help="JSONL event-log path ('off' disables)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace JSON (Perfetto-loadable) "
                         "of the run's spans to this path")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = reduce_config(cfg)

    if args.engine:
        from repro.obs import make_telemetry
        tel = make_telemetry(
            jsonl=(None if args.telemetry in (None, "off", "none", "")
                   else args.telemetry),
            run_id=f"serve-{args.arch}", workload="serve")
        if tel.enabled:
            tel.event("run_meta", workload="serve", arch=args.arch,
                      n_requests=args.engine, prompt_len=args.prompt_len,
                      steps=args.steps, max_batch=args.batch,
                      cache_capacity=args.cache_capacity)
        outputs, engine, _ = run_engine(
            cfg, args.engine, args.prompt_len, args.steps,
            max_batch=args.batch, cache_capacity=args.cache_capacity,
            telemetry=tel)
        print(f"[serve] engine: {len(outputs)} requests drained in "
              f"{engine.steps} decode steps; adapter cache {engine.adapters.stats()}")
        if tel.enabled:
            if args.trace_out:
                tel.export_chrome_trace(args.trace_out)
            tel.close()
            print(f"[telemetry] events -> {args.telemetry}"
                  + (f"  trace -> {args.trace_out}" if args.trace_out
                     else ""))
        return

    key = jax.random.PRNGKey(0)
    model = get_model(cfg)
    base = model.init_base(cfg, key)
    peft = init_peft(cfg, key, SpryConfig())
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0, cfg.vocab)
    total = args.prompt_len + args.steps

    # warmup: compile prefill + decode at the serving shapes OUTSIDE the
    # timed region (compile time otherwise dominates and the reported
    # "throughput" is mostly XLA)
    fns = build_serve_fns(cfg, model)
    greedy_generate(cfg, base, peft, prompt, 1, cache_len=total,
                    fns=fns).block_until_ready()

    t0 = time.time()
    ids = greedy_generate(cfg, base, peft, prompt, args.steps,
                          cache_len=total, fns=fns)
    ids.block_until_ready()
    e2e = time.time() - t0

    # steady-state decode throughput, separated from end-to-end latency
    # (which includes prompt ingestion)
    cache = model.init_cache(cfg, args.batch, total)
    tok = jnp.zeros((args.batch, 1), jnp.int32)
    logits, cache = fns["decode"](base, peft, cache, tok, jnp.int32(0))
    t0 = time.time()
    for s in range(args.steps):
        logits, cache = fns["decode"](base, peft, cache, tok,
                                      jnp.int32(1 + s))
    logits.block_until_ready()
    decode_tps = args.batch * args.steps / (time.time() - t0)

    print(f"[serve] {args.arch}: generated {ids.shape} in {e2e:.2f}s "
          f"end-to-end ({args.batch * args.steps / e2e:.1f} tok/s incl. "
          f"prefill); steady-state decode {decode_tps:.1f} tok/s; "
          f"sample row: {np.asarray(ids[0, :16])}")


if __name__ == "__main__":
    main()
