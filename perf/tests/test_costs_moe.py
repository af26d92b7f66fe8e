"""The counts behind ``mla_decode_roofline`` and ``expert_ffn_roofline``,
worked by hand, and the reason-sat mix's multiset."""
import json
import sys
from pathlib import Path

PERF = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(PERF))

from lib import kernel_costs_moe, traffic  # noqa: E402


def test_mla_decode_counts_one_stream_for_all_heads():
    fl, by = kernel_costs_moe.mla_decode([1000, 24], 128, 576, 512)
    assert fl == 2 * 128 * (1000 + 24) * (576 + 512)
    # the cache bytes do not grow with the heads; q and the output do
    assert by == ((1000 + 24) * 576 + 2 * 128 * (576 + 512)) * 2
    fl1, by1 = kernel_costs_moe.mla_decode([1000, 24], 1, 576, 512)
    assert by - by1 == 2 * 127 * (576 + 512) * 2 and fl == 128 * fl1


def test_expert_ffn_counts_hit_experts_once_and_no_padding():
    fl, by = kernel_costs_moe.expert_ffn(14, 64, 7168, 2048)
    assert fl == 2 * 3 * 64 * 7168 * 2048
    assert by == (3 * 14 * 7168 * 2048 + 2 * 64 * 7168) * 2


def test_reason_sat_multiset_is_what_the_file_says():
    req = json.loads((PERF / "traffic" / "reason-sat.json").read_text())[
        "requests"]
    pairs = traffic.multiset(req)
    assert len(pairs) == 512
    assert min(p for p, _ in pairs) == 64 and max(p for p, _ in pairs) == 256
    assert min(o for _, o in pairs) >= 512 and max(
        o for _, o in pairs) <= 3584
    assert max(p + o for p, o in pairs) <= 4096


def test_reference_one_precision_down_fails_the_cells_limits():
    """The reading the configuration's tolerances are set against, at the
    ``tiny`` width on the CPU: the reference with its activations rounded
    to float8_e4m3fn, following its own float32 routing, breaks the logit
    and the score limit; rounded to bfloat16 it keeps both. (At the
    configuration's width on the chip: PERF.md, PR 27.)"""
    import importlib.util

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, str(PERF.parent))
    from lib.compare import logit_gap_ulps
    from rlo_tpu.models.transformer import TransformerConfig, init_params

    cfg = json.loads((PERF / "configs" / "deepseek-v3-ep16.json").read_text())
    model, tol = cfg["tiny"]["model"], cfg["tolerance"]
    spec = importlib.util.spec_from_file_location(
        "dsv3_ref", PERF / "configs" / "deepseek-v3-ep16.py")
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    params = init_params(jax.random.PRNGKey(5), TransformerConfig(**model))
    tokens = jnp.asarray(traffic.token_ids(5, 0, 48, model["vocab"]))[None]

    want, records = ref.forward(params, tokens, model)
    forced = [jnp.asarray(r["ids"]) for r in records]
    readings = {}
    for name in ("bfloat16", "float8_e4m3fn"):
        ref.ACT_DTYPE = jnp.dtype(name)
        got, rounded = ref.forward(params, tokens, model, forced)
        readings[name] = (
            float(logit_gap_ulps(got, want)),
            max(float(np.abs(a["choice"] - b["choice"]).max())
                for a, b in zip(records, rounded)))
    ref.ACT_DTYPE = None
    logit8, score8 = readings["float8_e4m3fn"]
    logit16, score16 = readings["bfloat16"]
    assert logit8 > tol["logit_ulps_bf16"] and score8 > tol["score_eps"] / 2
    assert logit16 <= tol["logit_ulps_bf16"] and score16 <= tol["score_eps"] / 2
