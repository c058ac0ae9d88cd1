"""Batched serving driver (LM prefill+decode) with the paper's
runtime-tunability discipline: fixed-capacity compiled programs, model
swap = weight rewrite (no re-jit).

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b-smoke \
        --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.registry import get
from ..dist import sharding as shd
from ..dist.steps import make_decode_step, make_prefill_step
from ..models.api import family_for


class Server:
    """Fixed-shape serving engine: compiled once per (batch, prompt_cap,
    gen_cap).  The decode-cache capacity is ``prompt_cap + gen_cap``,
    fixed at construction, so every ``generate`` call reuses the same
    compiled prefill/decode programs regardless of the requested token
    count."""

    def __init__(self, cfg, mesh, *, batch: int, prompt_cap: int,
                 gen_cap: int = 16):
        self.cfg = cfg
        self.mesh = mesh
        shd.set_activation_mesh(mesh)
        self.fam = family_for(cfg)
        self.batch = batch
        self.prompt_cap = prompt_cap
        self.gen_cap = gen_cap
        self.cache_cap = prompt_cap + gen_cap
        self.prefill = jax.jit(make_prefill_step(cfg))
        self.decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))
        self.params = None

    def load_weights(self, params):
        """Model swap: pure data movement (the Fig-8 reprogram step)."""
        self.params = params

    def generate(self, prompts: np.ndarray, n_tokens: int) -> np.ndarray:
        """prompts: int32[B, prompt_len] -> int32[B, n_tokens].

        The prompt is right-padded to ``cache_cap = prompt_cap + gen_cap``
        so the compiled prefill allocates decode-capacity KV buffers
        (fixed-shape discipline); decode steps then fill slots
        sequentially, and the per-step kv_len mask hides not-yet-written
        slots."""
        B, plen = prompts.shape
        if plen > self.prompt_cap:
            raise ValueError(
                f"prompt length {plen} exceeds prompt_cap {self.prompt_cap}"
            )
        if n_tokens > self.gen_cap:
            raise ValueError(
                f"n_tokens {n_tokens} exceeds gen_cap {self.gen_cap}"
            )
        padded = np.zeros((B, self.cache_cap), np.int32)
        padded[:, :plen] = prompts
        logits, cache = self.prefill(self.params, {"tokens": jnp.asarray(padded)})
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        out = [np.asarray(tok)]
        for i in range(n_tokens - 1):
            tok, cache = self.decode(
                self.params, cache, {"token": tok, "pos": jnp.int32(plen + i)}
            )
            tok = tok[:, None] if tok.ndim == 1 else tok
            out.append(np.asarray(tok))
        return np.concatenate(out, axis=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()

    cfg = get(args.arch)
    mesh = shd.make_mesh((1, 1), ("data", "model"))
    # decode cache capacity (prompt + generation) is fixed at construction
    server = Server(cfg, mesh, batch=args.batch, prompt_cap=args.prompt_len,
                    gen_cap=args.gen)
    server.load_weights(family_for(cfg).init_params(cfg, jax.random.key(0)))

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)).astype(
        np.int32
    )
    t0 = time.time()
    tokens = server.generate(prompts, args.gen)
    dt = time.time() - t0
    print(f"generated {tokens.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    print(tokens[:, :8])


if __name__ == "__main__":
    main()
