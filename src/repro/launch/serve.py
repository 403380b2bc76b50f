DOC = """Serving launcher: batched generation against a (sharded) model.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
      --reduced --batch 4 --steps 16 [--quant 4]

--quant w runs every projection through w-bit packed bit-plane weights
(the CoMeFa path): at decode the weight stream out of HBM shrinks 16/w x,
which is the dominant term of the decode roofline (see EXPERIMENTS.md).
"""
import argparse


def main():
    ap = argparse.ArgumentParser(description=DOC)
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--quant", type=int, default=None)
    ap.add_argument("--reduced", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from repro import compile_cache, configs
    from repro.models import common, lm
    from repro.serve import engine

    compile_cache.enable()
    cfg = configs.get(args.arch, quant_bits=args.quant)
    if args.reduced:
        cfg = common.reduced(cfg, vocab=512, d_model=128, d_ff=256,
                             n_layers=max(len(cfg.pattern), 2),
                             quant_bits=args.quant)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0, cfg.vocab)
    enc = None
    if cfg.family == "encdec":
        enc = jax.random.normal(
            jax.random.PRNGKey(2),
            (args.batch, cfg.frontend_len, cfg.d_model), jnp.float32)
    out = engine.generate(params, prompt, cfg, steps=args.steps,
                          max_len=args.prompt_len + args.steps + 1,
                          temperature=args.temperature, enc_inputs=enc)
    print("generated token ids:")
    for row in out.tolist():
        print(" ", row)


if __name__ == "__main__":
    main()
