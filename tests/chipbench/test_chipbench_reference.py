"""The LM training reference keeps little on the device and computes what
the loop that kept every tree there computed.

``whole_tree_train`` below is that loop, kept as the oracle: the f32
parameters held for the whole run, every agent's error-feedback memory
and payload on the device, the aggregate made from the list of payloads.
``lm_reference.train`` must give the same numbers, digit for digit, on
the toy cut of each LM configuration of ``BENCHMARK.json`` and of the
q/k-norm configuration, under each side ``lm_train.controls`` reads: the
reference, the fp8 control and each planted fault.  Its device bytes at
each jitted call stay within 10 a parameter plus the largest leaf; the
oracle's do not.
"""
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chipbench_toy import TRIGGER_FAULTS, cells_of, toy_cell
from test_chipbench_new_arch import MIXES
from test_chipbench_new_arch import _cell as qknorm_cell

from benchmarks.chip import lm_reference
from benchmarks.chip.lm_reference import _int8, parse_comm
from benchmarks.chip.traffic import program_seed, token_batches

STEPS = 3
SEED = 2**31 + 5


def whole_tree_train(cfg, loss, params0, batches, comm, steps, lowp=False,
                     gain_scale=1.0):
    """The reference's loop with every tree on the device (the oracle)."""
    pol = parse_comm(comm)
    lr = float(cfg["train"]["lr"])
    pdt = jnp.dtype(cfg["train"]["dtype"])
    trig, args = pol["trigger"], pol["args"]
    vg = jax.jit(jax.value_and_grad(
        lambda p, t, y: loss(cfg, p, t, y, lowp)))
    f_loss = jax.jit(lambda p, t, y: loss(cfg, p, t, y, lowp))
    to32 = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), t))

    @jax.jit
    def wire(g, mem, alpha):
        g_eff = g if mem is None else jax.tree_util.tree_map(jnp.add, g, mem)
        sent = (jax.tree_util.tree_map(_int8, g_eff) if pol["stages"]
                else g_eff)
        resid = jax.tree_util.tree_map(lambda a, b: (a - b) * alpha, g_eff,
                                       sent)
        return sent, resid

    @jax.jit
    def apply(p32, agg):
        new = jax.tree_util.tree_map(
            lambda p, a: (p + (-lr * a).astype(pdt).astype(jnp.float32)
                          ).astype(pdt), p32, agg)
        return new, jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                           new)

    def probe_gain(p32, g, t, y, l0):
        eps = lr
        probe = jax.tree_util.tree_map(
            lambda p, gg: (p - eps * gg).astype(pdt).astype(jnp.float32),
            p32, g)
        return float(f_loss(probe, t, y)) - l0

    agents = batches[0]["tokens"].shape[0]
    p32 = to32(params0)
    mems = [None] * agents
    ctrl = [[float(args.get("lam0", 0.0)), 0.0, 0.0] for _ in range(agents)]
    out = {"loss": [], "gnorm": [], "gain": [], "ctrl": []}
    for s in range(steps):
        b = batches[s]
        sents, alphas, losses, gains = [], [], [], []
        for i in range(agents):
            t, y = b["tokens"][i], b["labels"][i]
            l0, g = vg(p32, t, y)
            l0 = float(l0)
            losses.append(l0)
            if trig == "grad_norm":
                gsq = gain_scale * float(sum(
                    jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
                alpha = float(gsq >= float(args.get("mu", 0.0)))
                gains.append(-lr * gsq)
            elif trig == "budget_dual":
                lam, sig, gmag = ctrl[i]
                gain = gain_scale * probe_gain(p32, g, t, y, l0)
                gains.append(gain)
                eta = float(args.get("eta", 0.5))
                beta = float(args.get("beta", 0.1))
                rate = float(args["rate"])
                alpha = float(gain <= -lam)
                gmag = (1 - beta) * gmag + beta * abs(gain)
                lam = max(lam + eta * (gmag + 0.25 * lam) * (alpha - rate),
                          0.0)
                ctrl[i] = [lam, (1 - beta) * sig + beta * alpha, gmag]
            elif trig == "always":
                alpha = 1.0
                gains.append(0.0)
            else:
                raise ValueError(f"reference has no trigger {trig!r}")
            sent, resid = wire(g, mems[i], jnp.float32(alpha))
            if pol["ef"]:
                mems[i] = resid
            sents.append(sent)
            alphas.append(alpha)
            del g
        denom = max(sum(alphas), 1.0)
        agg = jax.tree_util.tree_map(
            lambda *xs: sum(a * x for a, x in zip(alphas, xs)) / denom, *sents)
        del sents
        if s == 0:
            out["agg_leaf_norm"] = {k: float(jnp.linalg.norm(v))
                                    for k, v in agg.items()}
        out["gnorm"].append(float(jnp.sqrt(sum(
            jnp.sum(x * x) for x in jax.tree_util.tree_leaves(agg)))))
        out["loss"].append(float(np.mean(losses)))
        out["gain"].append(float(np.mean(gains)))
        if trig == "budget_dual":
            out["ctrl"].append([list(row) for row in ctrl])
        params, p32 = apply(p32, agg)
        del agg
    p0 = to32(params0)
    out["dparam_leaf_norm"] = {
        k: float(jnp.linalg.norm(params[k].astype(jnp.float32) - p0[k]))
        for k in params}
    return out


def _half(batches):
    return [{k: v[..., : v.shape[-1] // 2] for k, v in b.items()}
            for b in batches]


def _solo(batches):
    return [{k: v[:1] for k, v in b.items()} for b in batches]


# what each side of ``lm_train.controls`` hands the reference
SIDES = {
    "reference": lambda bs: (bs, {}),
    "control": lambda bs: (bs, {"lowp": True}),
    "half_batch": lambda bs: (_half(bs), {}),
    "no_exchange": lambda bs: (_solo(bs), {}),
    "gsq_halved": lambda bs: (bs, {"gain_scale": 0.5}),
    "probe_gain_zeroed": lambda bs: (bs, {"gain_scale": 0.0}),
}


def _trigger(comm: str) -> str:
    return comm.split("(")[0].split("|")[0].strip()


def _mix_of(name: str) -> str:
    cell = toy_cell(name)
    return cell.mix_name


# each LM cell of the benchmark, and the q/k-norm configuration under each
# LM mix, with every side its trigger has
LM_CELLS = ([(w, _mix_of(w)) for w in cells_of("lm_train")]
            + [(f"qknorm.{mix}", mix) for mix in MIXES])


def _cell(tmp_path, name: str):
    if name.startswith("qknorm."):
        return qknorm_cell(tmp_path, name.removeprefix("qknorm."),
                           qk_norm=True)
    return toy_cell(name)


def _sides(mix: str) -> list:
    comm = next(toy_cell(w) for w in cells_of("lm_train")
                if _mix_of(w) == mix).mix["comm"]
    return ["reference", "control", "half_batch", "no_exchange",
            TRIGGER_FAULTS[_trigger(comm)]]


CASES = [(name, side) for name, mix in LM_CELLS for side in _sides(mix)]


def _inputs(cell):
    """The cell's initial weights on the device and its first batches."""
    cfg = cell.cfg
    tr = cfg["train"]
    pseed = program_seed(SEED)
    params0 = cell.ref.init_params(cfg, pseed, jnp.dtype(tr["dtype"]))
    batches = token_batches(pseed, cell.mix["tokens"], agents=tr["agents"],
                            batch=tr["batch_per_agent"],
                            seq_len=cfg["seq_len"],
                            vocab=cfg["vocab_size"])[:STEPS]
    return params0, list(batches)


@pytest.mark.parametrize("name,side", CASES)
def test_reference_matches_the_whole_tree_loop(tmp_path, name, side):
    cell = _cell(tmp_path, name)
    params0, batches = _inputs(cell)
    bs, kw = SIDES[side](batches)
    comm = cell.mix["comm"]
    want = whole_tree_train(cell.cfg, cell.ref.loss, params0, bs, comm,
                            STEPS, **kw)
    got = cell.ref.train(cell.cfg, jax.device_get(params0), bs, comm, STEPS,
                         **kw)
    assert set(got) == set(want)
    for key in want:
        assert got[key] == want[key], key


def _peak_device_bytes(monkeypatch, run) -> int:
    """The most bytes of live device arrays at the entry of any jitted call
    ``run`` makes, above what was live before it started."""
    real = jax.jit
    readings = []

    def recording_jit(fun, *a, **k):
        jitted = real(fun, *a, **k)

        def call(*args, **kwargs):
            readings.append(sum(x.nbytes for x in jax.live_arrays()))
            return jitted(*args, **kwargs)
        return call

    def clear():
        # the reference makes its jitted calls once a process: make them
        # again under the recording ``jit``, and drop those afterwards
        for make in (lm_reference._losses, lm_reference._wire,
                     lm_reference._apply):
            make.cache_clear()

    gc.collect()
    base = sum(x.nbytes for x in jax.live_arrays())
    clear()
    with monkeypatch.context() as mp:
        mp.setattr(jax, "jit", recording_jit)
        run()
    clear()
    assert readings
    return max(readings) - base


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("loop", ["train", "whole_tree_loop"])
def test_reference_device_bytes(monkeypatch, loop, mix):
    workload = next(w for w in cells_of("lm_train") if _mix_of(w) == mix)
    cell = toy_cell(workload)
    params0, batches = _inputs(cell)
    host0 = jax.device_get(params0)
    del params0
    sizes = [int(np.prod(x.shape)) for x in host0.values()]
    bound = 10 * sum(sizes) + 4 * max(sizes)
    comm = cell.mix["comm"]
    if loop == "train":
        peak = _peak_device_bytes(monkeypatch, lambda: cell.ref.train(
            cell.cfg, host0, batches, comm, STEPS))
        assert peak <= bound, (peak / sum(sizes), bound / sum(sizes))
    else:
        # the loop this replaced holds every tree on the device: above 20
        # bytes a parameter at its second agent's calls
        peak = _peak_device_bytes(monkeypatch, lambda: whole_tree_train(
            cell.cfg, cell.ref.loss, jax.device_put(host0), batches, comm,
            STEPS))
        assert peak > 20 * sum(sizes), peak / sum(sizes)
