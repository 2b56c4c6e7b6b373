"""End-to-end training driver: event-triggered data-parallel training of
any assigned architecture on the deterministic synthetic LM stream.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --reduced --steps 200 --comm "gain_lookahead(lam=0.01)"

The communication stack is one ``--comm`` spec (repro.comm syntax):
trigger, then optional chained compressors, then ``+ef``::

    --comm "gain_lookahead(lam=0.01,decay=inv_t)|topk(0.05)|int8+ef"
    --comm "always|int8 ; never"     # per-agent heterogeneous (needs --agents 2)

The legacy ``--trigger/--lam/--mu/--period/--quantize/--topk/
--error-feedback`` flags still work and map onto the same spec.

The driver runs on whatever devices exist (CPU here, TPU pod in prod —
the mesh adapts).  Full assigned configs are for the dry-run/pod; on the
CPU box use ``--reduced`` (the same family, smoke-scale) or the default
``--d-model/--layers`` overrides for a ~100M-param run.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import checkpointer
from repro.configs import get_config, list_archs, reduced
from repro.configs.base import InputShape, TriggerConfig
from repro.core.api import init_train_state
from repro.data import synthetic as D
from repro.launch import steps as S
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build
from repro.optim import optimizers as opt_lib


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-135m", choices=list(list_archs()))
    ap.add_argument("--reduced", action="store_true", help="smoke-scale variant")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--vocab", type=int, default=None)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--agents", type=int, default=None, help="default: mesh data size")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "momentum", "adamw"])
    ap.add_argument("--comm", default=None, metavar="SPEC",
                    help="communication policy spec, e.g. "
                         "'gain_lookahead(lam=0.01)|topk(0.05)|int8+ef'; "
                         "';'-separated for per-agent policies. Supersedes "
                         "the legacy trigger/compression flags below.")
    # legacy flag spellings — assembled into a --comm spec when --comm is
    # not given:
    ap.add_argument("--trigger", default="gain_lookahead",
                    choices=["gain_lookahead", "gain_quadratic", "grad_norm",
                             "periodic", "always", "never"])
    ap.add_argument("--lam", type=float, default=0.0)
    ap.add_argument("--lam-decay", default="const",
                    choices=["const", "inv_t", "geometric"],
                    help="diminishing-λ schedule (paper eq.-23 remark)")
    ap.add_argument("--mu", type=float, default=0.0)
    ap.add_argument("--period", type=int, default=1)
    ap.add_argument("--quantize", action="store_true", help="int8 wire format")
    ap.add_argument("--topk", type=float, default=0.0,
                    help="top-k sparsified wire (fraction of entries kept)")
    ap.add_argument("--error-feedback", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--dtype", default="float32", choices=["float32", "bfloat16"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args()


def _legacy_comm_spec(args) -> str:
    """Assemble the legacy trigger/compression flags into a --comm spec."""
    from repro.comm import from_train_config
    from repro.configs.base import TrainConfig

    trig = TriggerConfig(kind=args.trigger, lam=args.lam, mu=args.mu,
                         period=args.period, lam_decay=args.lam_decay)
    legacy = TrainConfig(trigger=trig, quantize_grads=args.quantize,
                         topk_frac=args.topk,
                         error_feedback=args.error_feedback)
    return str(from_train_config(legacy))


def main():
    enable_compile_cache()
    args = parse_args()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    over = {}
    if args.layers:
        over["num_layers"] = args.layers
    if args.d_model:
        over["d_model"] = args.d_model
        over["head_dim"] = args.d_model // cfg.num_heads
    if args.vocab:
        over["vocab_size"] = args.vocab
    if over:
        cfg = cfg.replace(**over)

    mesh = make_host_mesh()
    shape = InputShape("train_cli", seq_len=args.seq, global_batch=args.batch,
                       kind="train")
    comm = args.comm or _legacy_comm_spec(args)
    plan = S.plan_run(cfg, shape, mesh, comm=comm, optimizer=args.optimizer,
                      lr=args.lr, microbatches=args.microbatches,
                      agents=args.agents)
    print(f"arch={cfg.name} params≈{cfg.param_count()/1e6:.1f}M agents={plan.num_agents} "
          f"comm={comm!r} mesh={dict(mesh.shape)}")

    jitted, *_ = S.build_train_step(mesh, plan, compute_dtype=args.dtype)
    model = build(plan.cfg.replace(compute_dtype=args.dtype))
    params, _ = model.init(jax.random.key(args.seed),
                           dtype=jnp.dtype(args.dtype))
    opt = opt_lib.from_config(plan.train_cfg)
    state = init_train_state(params, opt, plan.train_cfg)

    start = 0
    if args.resume and args.ckpt_dir and checkpointer.latest_step(args.ckpt_dir):
        state = checkpointer.restore(args.ckpt_dir, state)
        start = int(state.step)
        print(f"resumed from step {start}")

    tx_total, bytes_total, t0 = 0.0, 0.0, time.time()
    for step in range(start, args.steps):
        batch = D.lm_batch(cfg, shape, jax.random.key(10_000 + step),
                           num_agents=plan.num_agents)
        state, m = jitted(state, batch)
        tx_total += float(m["num_tx"])
        bytes_total += float(m["wire_bytes"])
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d}  loss {float(m['loss']):.4f}  "
                  f"comm_rate {float(m['comm_rate']):.2f}  "
                  f"gain {float(m['mean_gain']):+.2e}  "
                  f"|g| {float(m['grad_norm']):.3f}  "
                  f"({(time.time()-t0)/(step-start+1):.2f}s/step)", flush=True)
        if args.ckpt_every and args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            checkpointer.save(args.ckpt_dir, step + 1, state)

    total_rounds = (args.steps - start) * plan.num_agents
    print(f"\ndone: {args.steps - start} steps, transmissions {tx_total:.0f}/"
          f"{total_rounds} ({100 * tx_total / max(total_rounds, 1):.1f}% of dense), "
          f"effective wire {bytes_total / 1e6:.2f} MB")
    if args.ckpt_dir:
        checkpointer.save(args.ckpt_dir, args.steps, state)
        print(f"checkpoint -> {args.ckpt_dir}")


if __name__ == "__main__":
    main()
