DOC = """Serving driver: batched prefill + decode against a deployed model.

This is the client-side Inference Manager / Model Subscription API (paper
§VI) as a standalone service loop: a batch of requests is prefix-filled
once, then decoded token-by-token with the ring-buffer KV cache.

  PYTHONPATH=src python -m repro.launch.serve --arch fedforecast-100m \
      --batch 4 --prompt-len 64 --gen 16
"""

import argparse
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser(description=DOC)
    ap.add_argument("--arch", default="fedforecast-100m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full", dest="reduced", action="store_false")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    B, S = args.batch, args.prompt_len
    rng = np.random.default_rng(args.seed)

    if cfg.is_encoder_decoder:
        batch = {"frames": jnp.asarray(
                     rng.normal(size=(B, S, cfg.frontend.d_frontend))
                     .astype(np.float32)),
                 "tokens": jnp.asarray(
                     rng.integers(0, cfg.vocab, (B, S)).astype(np.int32))}
    elif cfg.frontend is not None:
        P_ = cfg.frontend.num_tokens
        batch = {"patches": jnp.asarray(
                     rng.normal(size=(B, P_, cfg.frontend.d_frontend))
                     .astype(np.float32)),
                 "tokens": jnp.asarray(
                     rng.integers(0, cfg.vocab, (B, max(S - P_, 8)))
                     .astype(np.int32))}
    else:
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab, (B, S)).astype(np.int32))}

    cache_len = model.cache_len_for(S + args.gen)
    prefill = jax.jit(model.prefill, static_argnums=2)
    decode = jax.jit(model.decode_step)

    t0 = time.time()
    logits, cache = prefill(params, batch, cache_len)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [np.asarray(tok)[:, 0]]
    t1 = time.time()
    for i in range(args.gen - 1):
        pos = jnp.full((B, 1), S + i, jnp.int32)
        logits, cache = decode(params, cache, tok, pos)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(np.asarray(tok)[:, 0])
    t_decode = time.time() - t1
    toks = np.stack(out, 1)
    print(f"arch={cfg.name} batch={B} prompt={S} gen={args.gen}")
    print(f"prefill: {t_prefill*1e3:.1f} ms "
          f"({B*S/max(t_prefill,1e-9):.0f} tok/s)")
    print(f"decode:  {t_decode*1e3:.1f} ms "
          f"({B*(args.gen-1)/max(t_decode,1e-9):.1f} tok/s)")
    print("sample continuation:", toks[0][:10].tolist())


if __name__ == "__main__":
    main()
