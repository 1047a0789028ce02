"""Serving drivers: synchronous fixed batch + continuous-batching stream.

`serve()` prefills one batch and decodes it in lockstep — the reference
path (and the parity oracle for the engine tests). `serve_stream()` drains
an async request stream through `repro.launch.scheduler.Engine`: queued
prompts are admitted into KV-cache slots as they free up mid-decode, so
the batch never idles on its slowest member (DESIGN §6).

Runs smoke configs on the host mesh in this container; the production
mesh path is exercised by the dry-run (same step functions, same
shardings).

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral_8x7b \
        --smoke --batch 4 --prompt-len 32 --gen 16
    PYTHONPATH=src python -m repro.launch.serve --arch llama3p2_3b \
        --smoke --stream --requests 16 --rate 64 --slots 4
"""
from __future__ import annotations

import argparse
import contextlib
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs.base import ARCH_IDS, get_config
from repro.dist import sharding as sh
from repro.launch import steps
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer
from repro.obs import jaxhooks as obs_jaxhooks
from repro.obs import registry as obs_registry
from repro.obs.metrics import fmt_seconds as _fmt_s


def serve(cfg, params, prompts, *, max_len: int, gen: int,
          mesh=None, frames=None, patches=None, greedy: bool = True,
          rng=None, temperature: float = 1.0):
    """prompts: (B, S) int32 -> generated tokens (B, gen) int32."""
    mesh = mesh or make_host_mesh()
    rules = sh.SERVE_RULES
    prefill = jax.jit(steps.make_prefill_step(cfg, max_len=max_len))
    decode = jax.jit(steps.make_decode_step(cfg), donate_argnums=(2,))

    with sh.use_mesh(mesh, rules):
        batch = {"tokens": prompts}
        if frames is not None:
            batch["frames"] = frames
        if patches is not None:
            batch["patches"] = patches
        logits, state = prefill(params, batch)
        outs = []
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        for i in range(gen):
            outs.append(tok)
            logits, state = decode(params, tok, state)
            lg = logits[:, -1]
            if greedy:
                tok = jnp.argmax(lg, axis=-1)[:, None].astype(jnp.int32)
            else:
                rng, sub = jax.random.split(rng)
                tok = jax.random.categorical(
                    sub, lg / temperature)[:, None].astype(jnp.int32)
        return jnp.concatenate(outs, axis=1)


def serve_stream(cfg, params, requests, *, slots: int, max_len: int,
                 mesh=None, greedy: bool = True, rng=None,
                 temperature: float = 1.0, realtime: bool = True,
                 verbose: bool = True, paged: bool = False,
                 block_size: int = 16, num_blocks=None,
                 prefill_batch: int = 1, bucket=None, clock=None):
    """Drain a request stream through the continuous-batching engine;
    returns (results, engine). `requests` is an iterable of
    `scheduler.Request` (see `scheduler.synth_request_stream`). With
    `paged=True` the engine serves from block-granular KV pools
    (DESIGN §13); block_size/num_blocks/prefill_batch pass through."""
    from repro.launch.scheduler import Engine
    eng = Engine(cfg, params, slots=slots, max_len=max_len, mesh=mesh,
                 greedy=greedy, rng=rng, temperature=temperature,
                 paged=paged, block_size=block_size, num_blocks=num_blocks,
                 prefill_batch=prefill_batch, bucket=bucket, clock=clock)
    results = eng.run(requests, realtime=realtime)
    if verbose:
        st = eng.stats()
        # every latency field is a None sentinel until a request
        # completes (stable stats schema) — the print must be None-safe,
        # not crash with a TypeError on an idle/zero-request run
        print(f"[serve] {cfg.name}: {st['requests']} requests, "
              f"{st['tokens']} tokens in {st['decode_steps']} decode steps "
              f"({st['tok_per_s']:.1f} tok/s, peak {st['peak_active']}/"
              f"{slots} slots)")
        if st["paged"]:
            print(f"[serve] paged: peak {st['peak_blocks']}/"
                  f"{st['num_blocks']} blocks of {st['block_size']} "
                  f"(contiguous worst case would pin "
                  f"{slots * (max_len // st['block_size'])})")
        print(f"[serve] latency mean/p50/p99/max = "
              f"{_fmt_s(st['latency_mean_s'])}/"
              f"{_fmt_s(st['latency_p50_s'])}/"
              f"{_fmt_s(st['latency_p99_s'])}/"
              f"{_fmt_s(st['latency_max_s'])} s, queue wait mean = "
              f"{_fmt_s(st['queue_wait_mean_s'])} s")
    return results, eng


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stream", action="store_true",
                    help="continuous batching: Poisson request stream "
                         "through the slot scheduler instead of one "
                         "synchronous batch")
    ap.add_argument("--requests", type=int, default=16,
                    help="[--stream] number of requests")
    ap.add_argument("--rate", type=float, default=64.0,
                    help="[--stream] Poisson arrival rate, req/s")
    ap.add_argument("--slots", type=int, default=None,
                    help="[--stream] cache slots (default: --batch)")
    ap.add_argument("--paged", action="store_true",
                    help="[--stream] block-granular paged KV: requests "
                         "reserve ceil(need/block-size) blocks instead of "
                         "a worst-case max_len row (DESIGN §13)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="[--paged] tokens per KV block (max_len must "
                         "divide evenly)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="[--paged] pool size; default = contiguous worst "
                         "case + null block")
    ap.add_argument("--prefill-batch", type=int, default=1,
                    help="[--paged] admit up to this many same-bucket "
                         "requests in one batched prefill launch")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="wrap the run in a jax.profiler trace written to "
                         "DIR (TensorBoard/Perfetto viewable; DESIGN §12)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write an obsmetrics/v1 METRICS.json snapshot of "
                         "the run (latency histograms, retrace counters, "
                         "prefill/decode spans) to PATH")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    # independent streams: the same key must never both initialise params
    # and sample data (prompt tokens correlated with embedding rows).
    key = jax.random.PRNGKey(args.seed)
    k_param, k_prompt, k_frames, k_patches = jax.random.split(key, 4)
    params = transformer.init_params(cfg, k_param, dtype=jnp.float32)

    def _run() -> int:
        if args.stream:
            from repro.launch.scheduler import synth_request_stream
            # patch tokens prepend to the decoder sequence -> cache rows
            max_len = (cfg.patch_tokens or 0) + args.prompt_len + args.gen + 1
            reqs = synth_request_stream(
                cfg, args.requests, rate=args.rate, seed=args.seed,
                prompt_lens=(max(1, args.prompt_len // 2), args.prompt_len),
                gen_lens=(max(1, args.gen // 2), args.gen))
            if args.paged and max_len % args.block_size:
                max_len += args.block_size - max_len % args.block_size
            serve_stream(cfg, params, reqs, slots=args.slots or args.batch,
                         max_len=max_len, paged=args.paged,
                         block_size=args.block_size,
                         num_blocks=args.num_blocks,
                         prefill_batch=args.prefill_batch)
            return 0

        prompts = jax.random.randint(
            k_prompt, (args.batch, args.prompt_len), 0, cfg.vocab_size,
            jnp.int32)
        kwargs = {}
        if cfg.encoder_layers:
            kwargs["frames"] = jax.random.normal(
                k_frames, (args.batch, cfg.encoder_frames,
                           cfg.d_model)) * 0.02
        if cfg.patch_tokens:
            kwargs["patches"] = jax.random.normal(
                k_patches, (args.batch, cfg.patch_tokens,
                            cfg.d_model)) * 0.02

        t0 = time.time()
        toks = serve(cfg, params, prompts,
                     max_len=(cfg.patch_tokens or 0) + args.prompt_len
                     + args.gen + 1,
                     gen=args.gen, **kwargs)
        dt = time.time() - t0
        print(f"[serve] {cfg.name}: generated {toks.shape} in {dt:.2f}s "
              f"({args.batch * args.gen / dt:.1f} tok/s)")
        print("[serve] sample:", toks[0, :12].tolist())
        return 0

    with contextlib.ExitStack() as stack:
        rec = None
        if args.metrics_out:
            rec = stack.enter_context(obs_registry.recording())
        stack.enter_context(obs_jaxhooks.profile_trace(args.profile))
        rc = _run()
        if rec is not None:
            obs_jaxhooks.record_device_memory(rec)
            rec.write(args.metrics_out)
            print(f"[serve] metrics: {len(rec.spans)} spans, "
                  f"{sum(c.value for c in rec.counters.values())} counter "
                  f"events -> {args.metrics_out}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
