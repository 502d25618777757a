"""Serving entry point: batched prefill + greedy decode in fixed waves.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
        --requests 16 --prompt-len 64 --gen-len 32 --batch 8

Requests arrive with ragged prompt lengths; ``main`` packs them into
waves of ``--batch``, left-pads each wave to ``--prompt-len``, prefills,
then decodes until every request of the wave has ``gen_len`` tokens.
Without ``--reduced`` the full config is served, which on one TPU takes
``--attn-impl pallas --dtype bf16``; the defaults (``ref`` attention,
f32 compute) are the CPU path.
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.models.params import split_params
from repro.models.runtime import Runtime
from repro.serve.serve_step import make_decode_step, make_prefill_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--tuning-db", default=None, metavar="PATH",
                    help="persisted TuningDB (benchmarks/kernel_sweep.py "
                         "output); tuned kernel tiles are picked up at "
                         "trace time")
    ap.add_argument("--attn-impl", default="ref",
                    choices=["ref", "chunked", "pallas"],
                    help="attention implementation (kernels/ops.py)")
    ap.add_argument("--dtype", default="f32", choices=["bf16", "f32"],
                    help="compute dtype (parameters stay f32)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    rt = Runtime(attn_impl=args.attn_impl, compute_dtype=args.dtype)
    if args.tuning_db:
        from repro.tuning.tundb import TuningDB
        rt = dataclasses.replace(rt, tuning_db=TuningDB(args.tuning_db))
    params, _ = split_params(model.init(jax.random.PRNGKey(0)))

    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=rng.integers(args.prompt_len // 2,
                                                          args.prompt_len + 1))
        for _ in range(args.requests)
    ]

    prefill = jax.jit(make_prefill_step(model, rt))
    decode = jax.jit(make_decode_step(model, rt), donate_argnums=(2,))
    cache_len = args.prompt_len + args.gen_len

    done, t0, tokens_out = [], time.perf_counter(), 0
    logprobs = [None] * len(prompts)
    queue = list(enumerate(prompts))
    while queue:
        wave = queue[: args.batch]
        queue = queue[args.batch:]
        B = args.batch
        toks = np.zeros((B, args.prompt_len), np.int32)
        for i, (_, p) in enumerate(wave):  # left-pad to a packed batch
            toks[i, args.prompt_len - len(p):] = p
        batch = {"tokens": jnp.asarray(toks)}
        if cfg.family == "vlm":
            batch["image_embeds"] = jnp.zeros(
                (B, cfg.num_frontend_tokens, cfg.d_model), jnp.float32)
        if cfg.encoder_layers:
            batch["encoder_embeds"] = jnp.zeros(
                (B, cfg.encoder_seq_len, cfg.d_model), jnp.float32)
        cache, _ = split_params(model.init_cache(B, cache_len))
        logits, cache = prefill(params, batch, cache)
        lp = np.asarray(jax.nn.log_softmax(logits[:, -1].astype(jnp.float32)))
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        outs = [tok]
        for _ in range(args.gen_len - 1):
            logits, cache = decode(params, tok, cache)
            tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
            outs.append(tok)
        gen = jnp.concatenate(outs, axis=1)
        jax.block_until_ready(gen)
        tokens_out += int(gen.size)
        for i, (rid, _) in enumerate(wave):
            done.append((rid, np.asarray(gen[i])))
            logprobs[rid] = lp[i]

    dt = time.perf_counter() - t0
    print(f"[serve] {len(done)} requests, {tokens_out} tokens in {dt:.2f}s "
          f"wall clock, compiles included (greedy, batch={args.batch})")
    return {"outputs": done, "prefill_logprobs": np.stack(logprobs),
            "seconds": dt}


if __name__ == "__main__":
    main()
