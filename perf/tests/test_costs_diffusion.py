"""The count behind ``block_attend_roofline``, worked by hand; the
blockgen-sat mix's multiset; the reader of ``diffusion_tokens_per_pass``."""
import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

PERF = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(PERF))

from lib import kernel_costs_diffusion, traffic  # noqa: E402


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, PERF / "layer_metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_block_attend_counts_a_rows_keys_once_for_the_whole_block():
    """Two rows whose blocks of 4 start at 96 and at 1000: 32 query heads
    in 4 groups of 128-wide K/V heads, bf16."""
    fl, by = kernel_costs_diffusion.block_attend([100, 1004], 32, 4, 128, 4)
    # QK^T and PV: 2 products x 2 ops x (4 x 32) query rows x ctx x 128
    assert fl == 2 * 2 * 4 * 32 * 128 * (100 + 1004) == 72_351_744
    # K and V of every attended position once: ctx x 4 x 128 x 2 tensors;
    # q in and o out: 4 x 32 x 128 each, a row; 2 bytes an element
    assert by == ((100 + 1004) * 4 * 128 * 2 + 2 * 2 * 4 * 32 * 128) * 2
    assert by == 2_392_064
    # the bytes of the cache do not grow with the block or the group
    fl1, by1 = kernel_costs_diffusion.block_attend([100, 1004], 4, 4, 128, 1)
    assert fl == 32 * fl1
    assert by - by1 == 2 * 2 * (4 * 32 - 4) * 128 * 2
    # at 819 GB/s and 197 TFLOP/s the attend is memory-bound: 8 rows a key
    assert fl / 197e12 < by / 819e9


def test_blockgen_sat_multiset_is_what_the_file_says():
    req = json.loads((PERF / "traffic" / "blockgen-sat.json").read_text())[
        "requests"]
    pairs = traffic.multiset(req)
    assert len(pairs) == 512
    prompts = sorted(p for p, _ in pairs)
    outputs = sorted(o for _, o in pairs)
    assert (prompts[0], prompts[-1]) == (64, 512)
    assert (outputs[0], outputs[-1]) == (256, 1024)
    assert 158 <= prompts[256] <= 162 and 508 <= outputs[256] <= 516
    assert max(p + o for p, o in pairs) <= 1536
    # lengths that 4 does and does not divide both occur
    assert {p % 4 for p in prompts} == {0, 1, 2, 3}


def test_tokens_per_pass_reads_the_programs_counters_or_nothing():
    read = _reader("diffusion_tokens_per_pass").read
    notes = []
    ctx = SimpleNamespace(note=notes.append, counters={
        "serve.diffusion.row_passes": 6000,
        "serve.diffusion.tokens_committed": 4800,
        "serve.diffusion.denoise_passes": 4800})
    assert read(ctx) == 0.8 and "row_passes 6000" in notes[0]
    # the parent has no such counters: the metric is left out
    assert read(SimpleNamespace(note=notes.append,
                                counters={"serve.tokens_out": 9})) is None


def test_block_attend_roofline_is_left_out_where_nothing_ran():
    read = _reader("block_attend_roofline").read
    reduced = SimpleNamespace(kernel_seconds={}, kernel_calls={})
    ctx = SimpleNamespace(reduced=reduced, facts={}, peaks=object(),
                          note=print)
    assert read(ctx) is None


def test_reference_one_precision_down_fails_the_cells_limits():
    """The reading the configuration's tolerances are set against, at the
    ``tiny`` width on the CPU: the reference with its activations rounded
    to float8_e4m3fn, following its own float32 routing, breaks the logit
    and the score limit; rounded to bfloat16 it keeps both. (At the
    configuration's width on the chip: PERF.md, PR 34.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, str(PERF.parent))
    from lib.compare import logit_gap_ulps
    from rlo_tpu.models.transformer import TransformerConfig, init_params

    cfg = json.loads((PERF / "configs" / "sdar-30b-a3b-6l.json").read_text())
    model, tol = cfg["tiny"]["model"], cfg["tiny"]["tolerance"]
    spec = importlib.util.spec_from_file_location(
        "sdar_ref", PERF / "configs" / "sdar-30b-a3b-6l.py")
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


def test_paired_cuts_keep_the_shares_and_steady_the_windows_completions():
    """The start hands out ``stationary_cut``'s own shares; what changes is
    who gets which. Of 192 requests, those left with 77 to 333 tokens end
    in rounds 3 to 13 (the timed window at 25.6 tokens a round): the
    lattice holds that count to a few, the shuffle lets it wander."""
    spec = importlib.util.spec_from_file_location(
        "serve_diffusion", PERF / "kinds" / "serve_diffusion.py")
    kind = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kind)
    req = json.loads((PERF / "traffic" / "blockgen-sat.json").read_text())[
        "requests"]

    def ending(seed, cuts):
        stream = traffic.ordered(req, seed)
        left = [max(1, round(next(stream)[1] * c)) for c in cuts]
        return sum(77 <= x <= 333 for x in left)

    paired, shuffled = [], []
    for seed in range(2_400_000_000, 2_400_000_040):
        cuts = kind.paired_cuts(req, seed, 192)
        plain = traffic.stationary_cut(req, seed, 192)
        assert sorted(cuts) == sorted(plain)
        assert kind.paired_cuts(req, seed, 192) == cuts
        paired.append(ending(seed, cuts))
        shuffled.append(ending(seed, plain))
    assert max(paired) - min(paired) <= 8 < 16 <= max(shuffled) - min(shuffled)
    assert abs(sum(paired) - sum(shuffled)) <= 2 * len(paired)
