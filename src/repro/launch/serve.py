"""Serving driver: continuous-batching decode engine over a ModelBus.

LM families (dense/moe) run on :class:`repro.serve.DecodeEngine` — one
persistent donated KV cache, requests admitted into free slots and retired
at scan-chunk boundaries, tokens generated by a jitted multi-step scan
instead of one host dispatch per token.  Other families (vlm/audio) keep
the legacy lockstep loop: batched prefill + per-token jit.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --reduced \
      --requests 8 --prompt-len 64 --new-tokens 32

``--no-reduced`` serves the full architecture; ``--no-greedy`` samples.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..models.registry import get_model
from ..serve import DecodeEngine, ModelBus
from .mesh import make_host_mesh

ENGINE_FAMILIES = ("dense", "moe")


def build_engine(cfg, bundle, *, slots: int, max_seq: int,
                 scan_chunk: int = 8, greedy: bool = True) -> DecodeEngine:
    """Random params (seed 0) published on a :class:`ModelBus`, served by
    a :class:`DecodeEngine`.  The init is jitted so the full-width f32
    random draws fuse into their low-precision outputs instead of living
    on the device as eager temporaries."""
    params = jax.jit(bundle.init)(jax.random.PRNGKey(0))
    return DecodeEngine(cfg, ModelBus(params), num_slots=slots,
                        max_seq=max_seq, scan_chunk=scan_chunk, greedy=greedy)


def serve_random_prompts(eng: DecodeEngine, *, requests: int,
                         prompt_len: int, new_tokens: int, seed: int = 1):
    """Submit ``requests`` random prompts and run the engine until it
    drains.  Returns ``({rid: prompt}, completions, wall seconds)``; the
    engine reads every chunk's tokens back to the host, so the time covers
    the device work."""
    rng = np.random.default_rng(seed)
    prompts = {}
    for _ in range(requests):
        prompt = [int(t) for t in rng.integers(0, eng.cfg.vocab_size,
                                               prompt_len)]
        prompts[eng.submit(prompt, max_new=new_tokens)] = prompt
    t0 = time.perf_counter()
    done = eng.run()
    return prompts, done, time.perf_counter() - t0


def _engine_serve(cfg, bundle, args) -> None:
    eng = build_engine(cfg, bundle, slots=args.slots,
                       max_seq=args.prompt_len + args.new_tokens,
                       scan_chunk=args.scan_chunk, greedy=args.greedy)
    _, done, dt = serve_random_prompts(eng, requests=args.requests,
                                       prompt_len=args.prompt_len,
                                       new_tokens=args.new_tokens)
    total = sum(len(c.tokens) for c in done)
    print(f"arch={cfg.name} slots={args.slots} requests={args.requests} "
          f"prompt={args.prompt_len} new={args.new_tokens} "
          f"greedy={args.greedy} window={cfg.sliding_window}")
    print(f"engine: {dt:.2f}s  {total} tokens  "
          f"({total / max(dt, 1e-9):.1f} tok/s)  "
          f"decode_steps={eng.stats['decode_steps']} "
          f"prefill_chunks={eng.stats['prefill_chunks']}")
    first = min(done, key=lambda c: c.rid)
    print("first sequence:", first.tokens[:16])


def _lockstep_serve(cfg, bundle, args) -> None:
    """Legacy path for families without slot decode (vlm/audio)."""
    mesh = make_host_mesh()
    max_seq = args.prompt_len + args.new_tokens + \
        (cfg.num_image_tokens if cfg.family == "vlm" else 0)

    key = jax.random.PRNGKey(0)
    params = bundle.init(key)
    prompt = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        prompt["image_embeds"] = jax.random.normal(
            key, (args.batch, cfg.num_image_tokens, cfg.d_model))
    if cfg.family == "audio":
        prompt["frames"] = jax.random.normal(
            key, (args.batch, cfg.max_source_positions, cfg.d_model))

    with mesh:
        t0 = time.time()
        logits, cache = jax.block_until_ready(
            jax.jit(lambda p, b: bundle.prefill(p, b, max_seq))(params, prompt))
        t_prefill = time.time() - t0
        decode = jax.jit(bundle.decode)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        toks = [tok]
        t0 = time.time()
        for _ in range(args.new_tokens - 1):
            logits, cache = decode(params, tok, cache)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            toks.append(tok)
        jax.block_until_ready(tok)
        t_decode = time.time() - t0

    total = args.batch * (args.new_tokens - 1)
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"window={cfg.sliding_window}")
    print(f"prefill: {t_prefill:.2f}s  decode: {t_decode:.2f}s "
          f"({total / max(t_decode, 1e-9):.1f} tok/s)")
    seq = np.stack([np.asarray(t) for t in toks], 1)
    print("first sequence:", seq[0][:16].tolist())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-7b")
    # BooleanOptionalAction so the defaults can actually be turned off:
    # the old `store_true, default=True` flags were stuck at True.
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--greedy", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--batch", type=int, default=4,
                    help="lockstep batch width (legacy families)")
    ap.add_argument("--slots", type=int, default=4,
                    help="engine decode slots (dense/moe families)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--scan-chunk", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    bundle = get_model(cfg)
    if cfg.family in ENGINE_FAMILIES:
        _engine_serve(cfg, bundle, args)
    else:
        _lockstep_serve(cfg, bundle, args)


if __name__ == "__main__":
    main()
