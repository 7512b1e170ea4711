"""Ziya-LLaMA inference demo.

Port of reference: fengshen/examples/ziya_inference/ (HF generation demo;
the reference also ships 8-bit/llama.cpp variants — see
generate_ziya_int8.py). Loads an HF llama checkpoint, applies the
"<human>:/<bot>:" chat format, and generates with sampling.

    python -m fengshen_tpu.examples.ziya_inference.generate_ziya \
        --model_path <hf-llama-dir> --query "帮我写一首诗" --top_p 0.85
"""

from __future__ import annotations

import argparse


def main(argv=None):
    import jax
    import jax.numpy as jnp
    from transformers import AutoTokenizer

    from fengshen_tpu.models.llama import LlamaForCausalLM
    from fengshen_tpu.models.llama.convert import load_hf_pretrained
    from fengshen_tpu.utils.generate import (generate,
                                             speculative_generate)

    parser = argparse.ArgumentParser()
    parser.add_argument("--model_path", required=True, type=str)
    parser.add_argument("--query", required=True, type=str)
    parser.add_argument("--max_new_tokens", default=128, type=int)
    parser.add_argument("--do_sample", action="store_true", default=True)
    parser.add_argument("--greedy", action="store_true", default=False,
                        help="force greedy decode (--do_sample defaults "
                             "on for reference parity and store_true "
                             "can't turn it off)")
    parser.add_argument("--temperature", default=0.8, type=float)
    parser.add_argument("--top_k", default=0, type=int)
    parser.add_argument("--top_p", default=0.85, type=float)
    parser.add_argument("--seed", default=42, type=int)
    parser.add_argument(
        "--draft_model_path", default=None, type=str,
        help="HF llama dir of a SMALL same-tokenizer draft model: "
             "switches to speculative decoding — greedy is token-exact "
             "vs plain greedy; with --do_sample the rejection scheme "
             "makes every token distributed exactly as plain sampling. "
             "The target runs once per 1..gamma+1 tokens")
    parser.add_argument("--gamma", default=4, type=int,
                        help="draft tokens proposed per verify forward")
    parser.add_argument(
        "--self_draft_layers", default=0, type=int,
        help="speculative decoding WITHOUT a second checkpoint: use the "
             "target's own first N layers (+ shared embeddings/norm/"
             "head) as the draft. Mutually exclusive with "
             "--draft_model_path")
    parser.add_argument(
        "--prompt_lookup", default=0, type=int,
        help="DRAFT-FREE speculation: propose the continuation of the "
             "latest earlier occurrence of the current N-gram suffix "
             "and verify with one target forward (token-exact greedy; "
             "big wins on extractive/repetitive outputs). Mutually "
             "exclusive with the draft flags")
    args = parser.parse_args(argv)
    if args.greedy:
        args.do_sample = False
    if sum(bool(x) for x in (args.draft_model_path,
                             args.self_draft_layers,
                             args.prompt_lookup)) > 1:
        raise SystemExit("--draft_model_path, --self_draft_layers and "
                         "--prompt_lookup are mutually exclusive")

    tokenizer = AutoTokenizer.from_pretrained(args.model_path)
    config, params = load_hf_pretrained(args.model_path)
    model = LlamaForCausalLM(config)

    prompt = f"<human>:{args.query.strip()}\n<bot>:"
    ids = tokenizer.encode(prompt)
    if args.draft_model_path or args.self_draft_layers:
        if args.self_draft_layers:
            from fengshen_tpu.models.llama import make_self_draft
            d_config, d_params = make_self_draft(
                config, params, args.self_draft_layers)
        else:
            d_config, d_params = load_hf_pretrained(
                args.draft_model_path)
        draft = LlamaForCausalLM(d_config)
        out, stats = speculative_generate(
            model, params, draft, d_params,
            jnp.asarray([ids], jnp.int32),
            max_new_tokens=args.max_new_tokens, gamma=args.gamma,
            do_sample=args.do_sample, temperature=args.temperature,
            top_k=args.top_k, top_p=args.top_p,
            eos_token_id=config.eos_token_id,
            pad_token_id=config.pad_token_id,
            rng=jax.random.PRNGKey(args.seed), return_stats=True)
        print(f"[speculative] rounds={int(stats['rounds'])} "
              f"accepted={int(stats['accepted'])}/"
              f"{int(stats['drafted'])} drafted")
    elif args.prompt_lookup:
        from fengshen_tpu.utils.generate import prompt_lookup_generate
        if args.do_sample:
            print("[prompt-lookup] greedy-only (no draft distribution "
                  "to reject against): ignoring sampling flags")
        out, stats = prompt_lookup_generate(
            model, params, jnp.asarray([ids], jnp.int32),
            max_new_tokens=args.max_new_tokens, gamma=args.gamma,
            ngram=args.prompt_lookup,
            eos_token_id=config.eos_token_id,
            pad_token_id=config.pad_token_id, return_stats=True)
        print(f"[prompt-lookup] rounds={int(stats['rounds'])} "
              f"accepted={int(stats['accepted'])}/"
              f"{int(stats['drafted'])} drafted")
    else:
        out = generate(model, params, jnp.asarray([ids], jnp.int32),
                       max_new_tokens=args.max_new_tokens,
                       do_sample=args.do_sample,
                       temperature=args.temperature,
                       top_k=args.top_k, top_p=args.top_p,
                       eos_token_id=config.eos_token_id,
                       pad_token_id=config.pad_token_id,
                       rng=jax.random.PRNGKey(args.seed))
    text = tokenizer.decode(list(out[0][len(ids):]),
                            skip_special_tokens=True)
    print(text.strip())


if __name__ == "__main__":
    main()
