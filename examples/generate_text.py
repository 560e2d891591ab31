"""Train a small LM and decode from it six ways — the serving tour.

Runs on jax's default device (set ``JAX_PLATFORMS=cpu`` for a CPU run, as
the tests do): trains a TransformerLM to memorize a
periodic token stream with the sync-DP trainer, then continues prompts
with each decoding recipe:

  1. generate       — exact fixed-buffer decoding (slides past max_len)
  2. generate_fast  — KV-cached, one compiled lax.scan
  3. generate_batch — N prompts through the same kernel
  4. beam_search    — best-scoring continuation with K beams
  5. generate_speculative — a smaller draft proposes, the target
     verifies; output identical to generate_fast for ANY draft
  6. Server         — continuous batching (requests arrive/finish at
     any time; results bit-equal to the solo calls)

Usage:  python examples/generate_text.py [--steps 150]
"""

import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import jax
import jax.numpy as jnp
import numpy as np
import optax

import mpit_tpu
from mpit_tpu.models import (
    Server,
    beam_search,
    generate,
    generate_batch,
    generate_fast,
    generate_speculative,
)
from mpit_tpu.models.transformer import TransformerLM
from mpit_tpu.parallel import DataParallelTrainer

V, T = 17, 32


def main():
    steps = 150
    if "--steps" in sys.argv:
        i = sys.argv.index("--steps") + 1
        if i >= len(sys.argv):
            print("--steps requires an argument", file=sys.stderr)
            raise SystemExit(2)
        steps = int(sys.argv[i])
        if steps < 1:
            print("--steps must be >= 1", file=sys.stderr)
            raise SystemExit(2)

    topo = mpit_tpu.init(num_workers=1)
    model = TransformerLM(
        vocab_size=V, num_layers=2, d_model=32, num_heads=4, max_len=T,
        compute_dtype=jnp.float32,
    )
    trainer = DataParallelTrainer(
        model, optax.adam(3e-3), topo, donate_state=False
    )
    stream = np.arange(8 * T * 2, dtype=np.int32) % V
    x = stream.reshape(-1, T)[:8]
    y = np.roll(x, -1, axis=1).astype(np.int32)
    state = trainer.init_state(jax.random.key(1), x[:1])
    for i in range(steps):
        state, m = trainer.step(state, x, y)
    print(f"trained {steps} steps, final loss {float(m['loss']):.4f}")

    prompt = list(range(8))
    print("prompt:", prompt, "(the stream continues 8, 9, 10, ... mod 17)")
    print("generate       :", generate(model, state.params, prompt, 8))
    greedy = generate_fast(model, state.params, prompt, 8)
    print("generate_fast  :", greedy)
    print("sampled t=0.7  :", generate_fast(
        model, state.params, prompt, 8, temperature=0.7, top_k=4, seed=0))
    outs = generate_batch(
        model, state.params, [prompt, [3, 4, 5], [11, 12]], 6
    )
    for row in outs:
        print("batched row    :", row)
    seq, score = beam_search(model, state.params, prompt, 8, beam_size=4)
    print(f"beam (K=4)     : {seq}   logprob {score:.3f}")

    # speculative: train a half-size draft on the same stream, then let
    # it propose — the output is the generate_fast greedy decode exactly
    draft = TransformerLM(
        vocab_size=V, num_layers=1, d_model=16, num_heads=2, max_len=T,
        compute_dtype=jnp.float32,
    )
    d_tr = DataParallelTrainer(
        draft, optax.adam(3e-3), topo, donate_state=False
    )
    d_state = d_tr.init_state(jax.random.key(2), x[:1])
    for _ in range(steps):
        d_state, _ = d_tr.step(d_state, x, y)
    spec, stats = generate_speculative(
        model, state.params, draft, d_state.params, prompt, 8, k=4,
        return_stats=True,
    )
    print(f"speculative    : {spec}   "
          f"({stats['mean_emitted']:.1f} tokens/verify-chunk)")
    assert spec == greedy  # the exactness contract, live

    # continuous batching: three requests, one resident-cache server
    srv = Server(model, state.params, max_batch=2, segment=4)
    rids = [srv.submit(q, 6) for q in (prompt, [3, 4, 5], [11, 12])]
    served = srv.drain()
    for rid in rids:
        print("served         :", served[rid])
    mpit_tpu.finalize()


if __name__ == "__main__":
    main()
