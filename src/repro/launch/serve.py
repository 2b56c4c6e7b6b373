"""Serving drivers: the one-shot decode demo and the streaming fleet
endpoint.

Decode demo (default) — prefill a prompt batch, then step the decode
loop (one token per request per step against the KV/state cache)::

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --reduced --batch 4 --prompt-len 32 --gen 16

Fleet mode (``--fleet``) — a continuous m=64 tiered training session
(:class:`repro.launch.session.FleetSession`): observation streams feed
the triggered train step round after round while the CommStats rollup
is served live as JSON (``/stats.json``) and Prometheus text
(``/metrics``)::

    PYTHONPATH=src python -m repro.launch.serve --fleet \
        --mix tiered_m64_adaptive --rounds 0 --telemetry-port 9100 \
        --telemetry-file /tmp/fleet.json --log-every 100

``--rounds 0`` serves until interrupted; ``--telemetry-port 0`` picks
an ephemeral port (printed on startup).  ``--ckpt-dir`` arms crash-safe
checkpointing: a killed run relaunched with the same directory
auto-resumes from the latest complete checkpoint (``--no-resume``
starts fresh).  Decode shapes in the dry-run lower exactly this
``decode_step``.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs, reduced
from repro.data import synthetic as D
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build

# the m=64 fleet scenarios --fleet can serve (repro.configs.paper_linreg)
FLEET_MIXES = (
    "tiered_m64", "tiered_m64_adaptive", "tiered_m64_edge_heavy",
    "tiered_m64_backbone_heavy", "tiered_m64_one_big",
    "tiered_m64_lossy", "tiered_m64_adaptive_lossy",
)


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-135m", choices=list(list_archs()))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=None)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    fleet = ap.add_argument_group("fleet mode")
    fleet.add_argument("--fleet", action="store_true",
                       help="run the streaming fleet session instead of "
                            "the decode demo")
    fleet.add_argument("--mix", default="tiered_m64_adaptive",
                       choices=FLEET_MIXES,
                       help="which m=64 tier mix to serve")
    fleet.add_argument("--rounds", type=int, default=0,
                       help="rounds to serve (0 = until interrupted)")
    fleet.add_argument("--lam-base", type=float, default=1.0)
    fleet.add_argument("--telemetry-port", type=int, default=None,
                       help="serve /stats.json + /metrics on this port "
                            "(0 = ephemeral)")
    fleet.add_argument("--telemetry-file", default=None,
                       help="write rollup JSON snapshots to this path")
    fleet.add_argument("--log-every", type=int, default=100,
                       help="rounds between stderr/file telemetry flushes")
    fleet.add_argument("--ckpt-dir", default=None,
                       help="crash-safe session checkpoints under this "
                            "directory (enables auto-resume on relaunch)")
    fleet.add_argument("--ckpt-every", type=int, default=50,
                       help="rounds between session checkpoints")
    fleet.add_argument("--no-resume", action="store_true",
                       help="ignore existing checkpoints in --ckpt-dir "
                            "and start fresh")
    fleet.add_argument("--watchdog", type=float, default=0.0,
                       help="seconds without a completed round before a "
                            "stall degradation event is logged (0 = off)")
    return ap.parse_args()


def serve_fleet(args) -> int:
    from repro.configs import paper_linreg as PL
    from repro.launch.session import (
        SessionOptions,
        build_linreg_fleet_session,
        file_sink,
    )

    net = getattr(PL, args.mix.upper())
    sink = None
    options = SessionOptions(
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        resume=not args.no_resume, watchdog_timeout=args.watchdog)
    session = build_linreg_fleet_session(
        net=net, lam_base=args.lam_base, seed=args.seed, options=options,
        on_round=lambda k, m: _fleet_log(session, sink, k, args.log_every))
    if args.ckpt_dir and session.round_index:
        print(f"resumed from checkpoint at round {session.round_index} "
              f"({args.ckpt_dir})", flush=True)
    if args.telemetry_file:
        sink = file_sink(args.telemetry_file, session.rollup,
                         every=args.log_every)
    server = None
    if args.telemetry_port is not None:
        server = session.serve_telemetry(port=args.telemetry_port)
        print(f"telemetry: {server.url}/stats.json  {server.url}/metrics",
              flush=True)
    print(f"fleet: mix={net.name} m={net.num_agents} "
          f"rounds={args.rounds or 'until-interrupted'}", flush=True)
    try:
        n = session.run(rounds=args.rounds)
    except KeyboardInterrupt:
        n = session.rollup.rounds
    finally:
        if args.ckpt_dir:
            session.checkpoint()
        if sink is not None:
            sink.flush()
        if server is not None:
            server.stop()
    snap = session.rollup.snapshot()
    print(f"served {n} rounds at {snap['rounds_per_sec']:.1f} rounds/s, "
          f"final loss {snap['gauges'].get('loss', float('nan')):.4f}",
          flush=True)
    return 0


def _fleet_log(session, sink, k, every):
    if sink is not None:
        sink(k, None)
    if every and (k + 1) % every == 0:
        s = session.rollup.snapshot()
        print(f"round {s['rounds']}: loss={s['gauges'].get('loss'):.4f} "
              f"comm_rate={s['gauges'].get('comm_rate'):.3f} "
              f"{s['rounds_per_sec_window']:.1f} rounds/s", flush=True)


def serve_decode(args) -> int:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if cfg.arch_type == "audio":
        raise SystemExit("whisper decoding is exercised via the dry-run decode "
                         "shapes; the CLI demo serves LM families")
    model = build(cfg)
    params, _ = model.init(jax.random.key(args.seed))
    cache_len = args.cache_len or (args.prompt_len + args.gen + 8)

    prompts = D.sample_lm_tokens(jax.random.key(7), args.batch,
                                 args.prompt_len, cfg.vocab_size)
    batch = {"tokens": prompts}
    if cfg.arch_type == "vlm":
        batch["patch_embeds"] = 0.02 * jax.random.normal(
            jax.random.key(8), (args.batch, cfg.num_patches, cfg.d_model))

    t0 = time.time()
    logits, cache = model.prefill(params, batch, cache_len=cache_len)
    jax.block_until_ready(cache)
    t_prefill = time.time() - t0
    last = logits[:, -1] if logits.ndim == 3 else logits[:, 0]

    decode = jax.jit(model.decode_step)
    key = jax.random.key(args.seed + 1)
    toks = jnp.argmax(last, axis=-1)[:, None].astype(jnp.int32)
    out_tokens = [toks]
    t0 = time.time()
    for i in range(args.gen - 1):
        pos = jnp.int32(args.prompt_len + i)
        logits, cache = decode(params, cache, toks, pos)
        if args.temperature > 0:
            key, k = jax.random.split(key)
            toks = jax.random.categorical(
                k, logits[:, 0] / args.temperature, axis=-1)[:, None].astype(jnp.int32)
        else:
            toks = jnp.argmax(logits[:, 0], axis=-1)[:, None].astype(jnp.int32)
        out_tokens.append(toks)
    jax.block_until_ready(toks)
    t_decode = time.time() - t0

    gen = jnp.concatenate(out_tokens, axis=1)
    print(f"arch={cfg.name} params≈{cfg.param_count()/1e6:.1f}M "
          f"batch={args.batch} cache_len={cache_len}")
    print(f"prefill: {args.prompt_len} tokens in {t_prefill:.2f}s")
    print(f"decode:  {args.gen} steps in {t_decode:.2f}s "
          f"({args.batch * args.gen / max(t_decode, 1e-9):.1f} tok/s batched)")
    for b in range(min(args.batch, 2)):
        print(f"request {b}: prompt…{prompts[b, -8:].tolist()} "
              f"-> {gen[b].tolist()}")
    return 0


def main():
    enable_compile_cache()
    args = parse_args()
    if args.fleet:
        raise SystemExit(serve_fleet(args))
    raise SystemExit(serve_decode(args))


if __name__ == "__main__":
    main()
