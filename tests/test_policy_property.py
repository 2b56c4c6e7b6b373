"""Property layer over the comm spec grammar (ISSUE-9 satellite).

``parse → str → parse`` must be the identity over the WHOLE composed
policy space — trigger × compressor chain × ``+ef`` × ``@ channel``
(``delay`` included) — not just the handful of hand-written examples
the per-feature tests pin.  Strategies draw from the registries' own
parameter tables with values inside each stage's validated range, so
every generated spec is one a user could legally write; rendering is
canonical (named args, declaration order, defaults omitted), so the
second parse must reproduce the first policy exactly AND the rendered
string must be a fixpoint.  Example-based round-trips below pin the
hand-written cases.
"""
import pytest

from hypothesis import given, is_hypothesis_test, settings
from hypothesis import strategies as st

from repro.comm import CommPolicy
from repro.net.channels import build_channel, spec_is_trivial

# (name, {param: draw-spec}) tables — value ranges sit strictly inside
# each stage's validated domain (see repro.comm.triggers/compressors and
# repro.net.channels), so parse failures are real grammar bugs
_F, _I, _CH = "float", "int", "choice"
TRIGGER_TABLE = (
    ("always", {}),
    ("never", {}),
    ("periodic", {"period": (_I, 1, 16)}),
    ("grad_norm", {"mu": (_F, 0.0, 16.0)}),
    ("gain_lookahead", {
        "lam": (_F, 0.0, 16.0),
        "decay": (_CH, ("const", "inv_t", "geometric")),
        "decay_rate": (_F, 0.5, 0.999),
    }),
    ("gain_estimated", {
        "lam": (_F, 0.0, 16.0),
        "decay": (_CH, ("const", "inv_t", "geometric")),
        "decay_rate": (_F, 0.5, 0.999),
    }),
    ("budget_dual", {
        "rate": (_F, 0.01, 1.0), "eta": (_F, 0.01, 2.0),
        "lam0": (_F, 0.0, 4.0), "beta": (_F, 0.01, 1.0),
    }),
    ("budget_window", {
        "bytes": (_F, 1.0, 1e4), "window": (_I, 1, 64),
        "eta": (_F, 0.01, 2.0), "lam0": (_F, 0.0, 4.0),
        "beta": (_F, 0.01, 1.0),
    }),
)
COMPRESSOR_TABLE = (
    ("identity", {}),
    ("fp16", {}),
    ("bf16", {}),
    ("int8", {}),
    ("topk", {"frac": (_F, 1e-3, 1.0)}),
    ("randk", {"frac": (_F, 1e-3, 1.0), "seed": (_I, 0, 99)}),
    ("sketch", {"rows": (_I, 1, 8), "cols": (_I, 1, 256),
                "seed": (_I, 0, 99)}),
)
CHANNEL_TABLE = (
    ("ideal", {}),
    ("bernoulli", {"p": (_F, 0.0, 1.0), "boost": (_F, 0.0, 1.0),
                   "seed": (_I, 0, 99)}),
    ("gilbert_elliott", {
        "p_gb": (_F, 0.0, 1.0), "p_bg": (_F, 0.0, 1.0),
        "p_loss_good": (_F, 0.0, 1.0), "p_loss_bad": (_F, 0.0, 1.0),
        "boost": (_F, 0.0, 1.0), "seed": (_I, 0, 99),
    }),
    ("rate", {"bytes_per_round": (_F, 1.0, 1e4), "burst": (_F, 1.0, 16.0),
              "boost": (_F, 0.0, 1.0)}),
    # delay's lag must satisfy 1 <= lag <= max_lag — drawn jointly below
    ("delay", {"dist": (_CH, ("geometric", "deterministic")),
               "max_lag": (_I, 1, 6), "discount": (_F, 0.0, 4.0),
               "boost": (_F, 0.0, 1.0), "seed": (_I, 0, 99)}),
    # retx's p only composes with the (default) bernoulli inner model —
    # the model override is drawn jointly below
    ("retx", {"k": (_I, 1, 4), "fresh": (_CH, ("true", "false")),
              "p": (_F, 0.0, 1.0), "boost": (_F, 0.0, 1.0),
              "seed": (_I, 0, 99)}),
)


def _draw_value(data, spec):
    kind = spec[0]
    if kind == _F:
        return data.draw(st.floats(spec[1], spec[2], allow_nan=False,
                                   allow_infinity=False))
    if kind == _I:
        return data.draw(st.integers(spec[1], spec[2]))
    return data.draw(st.sampled_from(spec[1]))


def _draw_stage(data, table):
    """One random ``name(k=v,...)`` stage text from a registry table.

    Each parameter is independently included or left at its default, so
    the corpus covers the defaults-render-away paths too.
    """
    name, params = data.draw(st.sampled_from(table))
    args = {}
    for key, spec in params.items():
        if data.draw(st.booleans()):
            args[key] = _draw_value(data, spec)
    if name == "delay" and "max_lag" in args:
        # respect the channel's 1 <= lag <= max_lag validation; below
        # the default lag (2.0) a lag must be drawn
        if args["max_lag"] < 2 or data.draw(st.booleans()):
            args["lag"] = data.draw(st.floats(
                1.0, float(args["max_lag"]), allow_nan=False,
                allow_infinity=False))
    if name == "retx" and data.draw(st.booleans()):
        # p is only a bernoulli knob — a non-bernoulli inner model
        # rejects it, so the draws stay jointly valid
        args["model"] = data.draw(
            st.sampled_from(("bernoulli", "gilbert_elliott")))
        if args["model"] != "bernoulli":
            args.pop("p", None)
    if not args:
        return name
    body = ",".join(f"{k}={v!r}" if isinstance(v, str) else f"{k}={v}"
                    for k, v in args.items())
    # spec strings carry bare strings, not Python quotes
    body = body.replace("'", "")
    return f"{name}({body})"


def _draw_policy_text(data):
    parts = [_draw_stage(data, TRIGGER_TABLE)]
    n_comp = data.draw(st.integers(0, 3))
    for _ in range(n_comp):
        parts.append(_draw_stage(data, COMPRESSOR_TABLE))
    text = "|".join(parts)
    if n_comp and data.draw(st.booleans()):
        text += "+ef"
    if data.draw(st.booleans()):
        text += f" @ {_draw_stage(data, CHANNEL_TABLE)}"
    return text


@given(data=st.data())
@settings(max_examples=200, deadline=None)
def test_policy_round_trip_property(data):
    """parse(render(parse(spec))) == parse(spec), render is a fixpoint."""
    text = _draw_policy_text(data)
    pol = CommPolicy.parse_one(text)
    rendered = str(pol)
    pol2 = CommPolicy.parse_one(rendered)
    assert pol2 == pol, (text, rendered)
    assert str(pol2) == rendered, (text, rendered)
    # channel values were drawn inside the validated domain, so the
    # round-tripped spec must also BUILD (delay depth/lag checks etc.)
    if pol.channel is not None and not spec_is_trivial(pol.channel):
        assert build_channel(pol.channel) is not None


@given(data=st.data())
@settings(max_examples=50, deadline=None)
def test_hetero_policy_round_trip_property(data):
    """';'-joined per-agent specs round-trip policy-for-policy."""
    n = data.draw(st.integers(1, 4))
    texts = [_draw_policy_text(data) for _ in range(n)]
    pols = CommPolicy.parse(" ; ".join(texts))
    if n == 1:
        pols = (pols,)
    assert len(pols) == n
    rejoined = " ; ".join(str(p) for p in pols)
    pols2 = CommPolicy.parse(rejoined)
    if n == 1:
        pols2 = (pols2,)
    assert tuple(pols2) == tuple(pols)


# ----------------------------------------------------------------------
# example-based round trips — run with or without hypothesis
# ----------------------------------------------------------------------

EXAMPLES = (
    "always",
    "never @ ideal",
    "periodic(period=3)|int8",
    "grad_norm(mu=4.0)|topk(0.05)|int8+ef",
    "gain_lookahead(lam=0.1,decay=geometric,decay_rate=0.9)|fp16",
    "budget_dual(rate=0.3,eta=0.05)|sketch(rows=3,cols=32,seed=7)+ef"
    " @ bernoulli(p=0.2,boost=0.05,seed=3)",
    "budget_window(bytes=448.0)|fp16 @ rate(bytes_per_round=64.0,burst=2.0)",
    "always|topk(0.5)|int8+ef"
    " @ delay(dist=deterministic,lag=3.0,max_lag=4,discount=1.0,seed=5)",
    "gain_lookahead(lam=2.0)|bf16+ef @ delay(discount=0.5)",
    "always @ delay(dist=geometric,lag=2.0,max_lag=6)",
    "gain_lookahead(lam=2.0)|int8 @ retx(k=2,fresh=true,p=0.25,seed=3)",
    "always|topk(0.5)+ef @ retx",
    "grad_norm(mu=1.0)|int8 @ retx(k=3,model=gilbert_elliott,seed=1)",
)


@pytest.mark.parametrize("text", EXAMPLES)
def test_policy_round_trip_examples(text):
    pol = CommPolicy.parse_one(text)
    rendered = str(pol)
    pol2 = CommPolicy.parse_one(rendered)
    assert pol2 == pol
    assert str(pol2) == rendered


def test_delay_defaults_render_away():
    """The all-defaults delay spec renders bare, like every stage."""
    pol = CommPolicy.parse_one(
        "always @ delay(dist=geometric,lag=2.0,max_lag=4,discount=0.0,"
        "boost=0.0,seed=0)")
    assert str(pol) == "always @ delay"
    assert CommPolicy.parse_one(str(pol)) == pol


def test_retx_defaults_render_away():
    """The all-defaults retx spec renders bare, like every stage."""
    pol = CommPolicy.parse_one(
        "always @ retx(k=1,fresh=false,p=0.1,model=bernoulli,boost=0.0,"
        "seed=0)")
    assert str(pol) == "always @ retx"
    assert CommPolicy.parse_one(str(pol)) == pol


def test_property_layer_is_active():
    """Bookkeeping: the round-trip properties run under hypothesis."""
    assert is_hypothesis_test(test_policy_round_trip_property)
    assert is_hypothesis_test(test_hetero_policy_round_trip_property)
