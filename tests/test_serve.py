"""Continuous batching (models.serve) — the scheduling-not-numerics
oracle: every request's tokens equal its dense `generate` exactly, for
any stream shape (more requests than slots, mixed lengths/budgets,
late submissions, eos early-exit, int8/GQA configs)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rlo_tpu.models.generate import generate
from rlo_tpu.models.serve import DecodeServer, _bucket
from rlo_tpu.models.transformer import TransformerConfig, init_params

CFG = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                        d_ff=64, dtype="float32")


@pytest.fixture(scope="module")
def setup():
    params = init_params(jax.random.PRNGKey(0), CFG)
    return params


def dense_oracle(params, cfg, prompt, max_new):
    out = generate(params, jnp.asarray(prompt, jnp.int32)[None, :],
                   cfg, max_new=max_new)
    return np.asarray(out)[0]


def test_stream_matches_dense(setup):
    """8 requests through 3 slots, mixed prompt lengths and budgets —
    each result equals its dense generate."""
    params = setup
    rng = np.random.default_rng(0)
    srv = DecodeServer(params, CFG, n_slots=3, max_len=96,
                       round_len=5, prompt_buckets=(8, 16, 32))
    reqs = []
    for i in range(8):
        plen = int(rng.integers(3, 30))
        max_new = int(rng.integers(1, 20))
        prompt = rng.integers(0, CFG.vocab, (plen,))
        reqs.append((prompt, max_new))
        srv.submit(prompt, max_new)
    outs = srv.run()
    assert len(outs) == 8
    for (prompt, max_new), got in zip(reqs, outs):
        want = dense_oracle(params, CFG, prompt, max_new)
        np.testing.assert_array_equal(got, want)


def test_late_submission_joins_running_batch(setup):
    """Requests submitted while the loop is running fill freed slots
    mid-stream."""
    params = setup
    rng = np.random.default_rng(1)
    srv = DecodeServer(params, CFG, n_slots=2, max_len=64,
                       round_len=4, prompt_buckets=(8, 16))
    first = [(rng.integers(0, CFG.vocab, (5,)), 6),
             (rng.integers(0, CFG.vocab, (9,)), 14)]
    for p, m in first:
        srv.submit(p, m)
    srv.step_round()  # both running
    late = (rng.integers(0, CFG.vocab, (12,)), 9)
    srv.submit(*late[:1], late[1])
    outs = srv.run()
    for (p, m), got in zip(first + [late], outs):
        np.testing.assert_array_equal(got,
                                      dense_oracle(params, CFG, p, m))


def test_eos_frees_slot_early(setup):
    """eos truncates the output (eos included) and frees the slot; a
    queued request then completes. Oracle: dense generate truncated at
    its own first eos."""
    params = setup
    rng = np.random.default_rng(2)
    # find an eos id that actually occurs early in some dense output
    prompt = rng.integers(0, CFG.vocab, (7,))
    dense = dense_oracle(params, CFG, prompt, 16)
    eos = int(dense[3])
    srv = DecodeServer(params, CFG, n_slots=1, max_len=64,
                       round_len=4, prompt_buckets=(8,))
    srv.submit(prompt, 16, eos_id=eos)
    p2 = rng.integers(0, CFG.vocab, (6,))
    srv.submit(p2, 5)
    outs = srv.run()
    want = dense[:list(dense).index(eos) + 1]
    np.testing.assert_array_equal(outs[0], want)
    np.testing.assert_array_equal(outs[1],
                                  dense_oracle(params, CFG, p2, 5))


@pytest.mark.parametrize("variant", ["gqa_rope", "int8"])
def test_variants(setup, variant):
    cfg = (dataclasses.replace(CFG, n_kv_heads=2, pos_encoding="rope")
           if variant == "gqa_rope"
           else dataclasses.replace(CFG, kv_cache_dtype="int8"))
    params = init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(3)
    srv = DecodeServer(params, cfg, n_slots=2, max_len=64,
                       round_len=3, prompt_buckets=(8, 16))
    reqs = [(rng.integers(0, cfg.vocab, (int(rng.integers(3, 14)),)),
             int(rng.integers(2, 10))) for _ in range(5)]
    for p, m in reqs:
        srv.submit(p, m)
    outs = srv.run()
    for (p, m), got in zip(reqs, outs):
        np.testing.assert_array_equal(got,
                                      dense_oracle(params, cfg, p, m))


def test_slot_reuse_no_stale_leak(setup):
    """A short request reuses a slot that previously held a LONGER
    sequence — stale cache beyond the new row's positions must never
    be attended."""
    params = setup
    rng = np.random.default_rng(4)
    srv = DecodeServer(params, CFG, n_slots=1, max_len=64,
                       round_len=8, prompt_buckets=(8, 32))
    long_p = rng.integers(0, CFG.vocab, (30,))
    short_p = rng.integers(0, CFG.vocab, (4,))
    srv.submit(long_p, 12)
    srv.submit(short_p, 12)
    outs = srv.run()
    np.testing.assert_array_equal(
        outs[0], dense_oracle(params, CFG, long_p, 12))
    np.testing.assert_array_equal(
        outs[1], dense_oracle(params, CFG, short_p, 12))


def test_long_prompt_exceeds_largest_bucket(setup):
    """Prompts LONGER than the largest bucket are admissible now:
    admission prefills the bucket-sized head and extends through
    jitted block_decode chunks — dense-generate parity holds for any
    plen <= max_len - max_new (docs/DESIGN.md §12 satellite)."""
    params = setup
    rng = np.random.default_rng(13)
    srv = DecodeServer(params, CFG, n_slots=2, max_len=96,
                       round_len=4, prompt_buckets=(8, 16))
    reqs = [(rng.integers(0, CFG.vocab, (30,)), 10),   # 1 chunk
            (rng.integers(0, CFG.vocab, (41,)), 7),    # 2 chunks
            (rng.integers(0, CFG.vocab, (5,)), 6)]     # in-bucket
    for p, m in reqs:
        srv.submit(p, m)
    outs = srv.run()
    for (p, m), got in zip(reqs, outs):
        np.testing.assert_array_equal(got,
                                      dense_oracle(params, CFG, p, m))


def test_errors(setup):
    srv = DecodeServer(setup, CFG, n_slots=1, max_len=16,
                       prompt_buckets=(8,))
    with pytest.raises(ValueError, match="max_len"):
        srv.submit(np.zeros(8, np.int32), 20)
    with pytest.raises(ValueError, match="bucket"):
        _bucket(100, (8, 16))


def test_poll_completed_and_cancel(setup):
    """The fabric-facing hooks (docs/DESIGN.md §11): poll_completed
    drains (rid, tokens) incrementally and matches the dense oracle;
    cancel frees a slot mid-decode (or de-queues) so ownership can
    move; canceled requests never complete and free capacity for the
    rest of the stream."""
    from rlo_tpu.utils.metrics import Registry

    params = setup
    rng = np.random.default_rng(11)
    reg = Registry()
    srv = DecodeServer(params, CFG, n_slots=2, max_len=64,
                       round_len=4, prompt_buckets=(8, 16),
                       metrics=reg)
    reqs = [(rng.integers(0, CFG.vocab, (5,)), 10),
            (rng.integers(0, CFG.vocab, (7,)), 6),
            (rng.integers(0, CFG.vocab, (4,)), 8)]
    rids = [srv.submit(p, m) for p, m in reqs]
    assert srv.has_work() and srv.queue_depth() == 3
    srv.step_round()  # admits rids 0+1 into the 2 slots
    assert srv.queue_depth() == 1
    assert set(srv.slot_ownership()) <= {rids[0], rids[1], None}
    assert srv.cancel(rids[0]) is True          # in-slot cancel
    assert srv.cancel(rids[0]) is False         # idempotent
    outs = srv.run()
    got = dict()
    for rid, toks in srv.poll_completed():
        got[rid] = toks
    assert srv.poll_completed() == []           # drained
    assert set(got) == {rids[1], rids[2]}       # canceled never lands
    for i in (1, 2):
        p, m = reqs[i]
        np.testing.assert_array_equal(got[rids[i]],
                                      dense_oracle(params, CFG, p, m))
        np.testing.assert_array_equal(outs[rids[i]], got[rids[i]])
    snap = srv.stats()
    assert snap["counters"]["serve.requests_canceled"] == 1
    assert snap["counters"]["serve.requests_completed"] == 2
    # e2e latency (submit -> last token) recorded per completion only
    assert snap["histograms"]["serve.e2e_usec"]["count"] == 2
    assert snap["histograms"]["serve.e2e_usec"]["p50"] is not None
    assert srv.free_slots() == 2 and not srv.has_work()


def test_cancel_queued_before_admission(setup):
    """A request canceled while still queued never prefills; run()
    returns an empty row for it and the stream completes."""
    params = setup
    rng = np.random.default_rng(12)
    srv = DecodeServer(params, CFG, n_slots=1, max_len=64,
                       round_len=4, prompt_buckets=(8,))
    r0 = srv.submit(rng.integers(0, CFG.vocab, (5,)), 6)
    r1 = srv.submit(rng.integers(0, CFG.vocab, (6,)), 4)
    assert srv.cancel(r1) is True
    outs = srv.run()
    assert len(outs[r0]) == 6 and len(outs[r1]) == 0


def test_serving_telemetry(setup):
    """Serving telemetry (docs/DESIGN.md §7): every request's TTFT and
    queue wait are recorded, the round histogram and the slot-step
    counters advance, and the token counter equals the emitted tokens — without perturbing
    the scheduling oracle (outputs still equal dense generate)."""
    from rlo_tpu.utils.metrics import Registry

    params = setup
    rng = np.random.default_rng(7)
    reg = Registry()
    srv = DecodeServer(params, CFG, n_slots=2, max_len=64,
                       round_len=4, prompt_buckets=(8, 16),
                       metrics=reg)
    reqs = [(rng.integers(0, CFG.vocab, (int(rng.integers(3, 12)),)),
             int(rng.integers(1, 7))) for _ in range(3)]
    for p, m in reqs:
        srv.submit(p, m)
    outs = srv.run()
    for (p, m), got in zip(reqs, outs):
        np.testing.assert_array_equal(got,
                                      dense_oracle(params, CFG, p, m))

    snap = srv.stats()
    c, h = snap["counters"], snap["histograms"]
    assert c["serve.requests_submitted"] == 3
    assert c["serve.requests_completed"] == 3
    assert c["serve.tokens_out"] == sum(len(o) for o in outs)
    assert h["serve.ttft_usec"]["count"] == 3
    assert h["serve.queue_wait_usec"]["count"] == 3
    assert h["serve.round_usec"]["count"] == srv.rounds_run >= 1
    # the per-token time is round_usec / kk and occupancy is the two
    # slot-step counters: every round computed kk x n_slots slot-steps,
    # of which the tokens the rounds kept were useful
    assert c["serve.slot_steps"] == srv.steps_run * srv.n_slots
    assert 0 < c["serve.slot_steps_useful"] <= c["serve.slot_steps"]
    assert c["serve.slot_steps_useful"] == \
        c["serve.tokens_out"] - c["serve.admissions"]
    assert snap["gauges"]["serve.queue_depth"] == 0
    # stats() emits percentile summaries (not raw bucket dumps): the
    # quantiles are exact over the kept samples, so they are ordered
    # and bracketed by min/max with no log2 slack
    ttft = h["serve.ttft_usec"]
    assert ttft["min"] <= ttft["p50"] <= ttft["p90"] <= ttft["p99"]
    assert ttft["p99"] <= ttft["max"]
    assert "buckets" not in ttft
    # TTFT >= queue wait for the same request set (it includes it);
    # counts are equal so the mean comparison is the old sum one
    assert ttft["mean"] >= h["serve.queue_wait_usec"]["mean"]


def test_generate_timed_matches_generate_and_records(setup):
    """generate_timed: exact token parity with generate() plus TTFT /
    per-token records into its registry (the DecodeServer-shared
    schema)."""
    from rlo_tpu.models.generate import generate_timed
    from rlo_tpu.utils.metrics import Registry

    params = setup
    rng = np.random.default_rng(9)
    prompt = jnp.asarray(rng.integers(0, CFG.vocab, (2, 6)), jnp.int32)
    reg = Registry()
    got = np.asarray(generate_timed(params, prompt, CFG, max_new=5,
                                    metrics=reg))
    want = np.asarray(generate(params, prompt, CFG, max_new=5))
    np.testing.assert_array_equal(got, want)
    snap = reg.snapshot()
    assert snap["histograms"]["serve.ttft_usec"]["count"] == 1
    assert snap["histograms"]["serve.tok_usec"]["count"] == 1
    assert snap["counters"]["serve.tokens_out"] == 2 * 5
    assert snap["histograms"]["serve.ttft_usec"]["min"] > 0

    # sampling path: same key stream -> same tokens as generate()
    key = jax.random.PRNGKey(0)
    got_s = np.asarray(generate_timed(params, prompt, CFG, max_new=3,
                                      temperature=0.7, rng=key,
                                      metrics=reg))
    want_s = np.asarray(generate(params, prompt, CFG, max_new=3,
                                 temperature=0.7, rng=key))
    np.testing.assert_array_equal(got_s, want_s)


# ---------------------------------------------------------------------------
# spans and counters inside the server (docs/DESIGN.md §7)
# ---------------------------------------------------------------------------

#: child stage -> the stage whose span encloses it
SPAN_PARENT = {
    "admit": "step_round", "round.dispatch": "step_round",
    "round.wait": "step_round", "round.readback": "step_round",
    "distribute": "step_round", "page_gauges": "step_round",
    "admit.stage_input": "admit", "admit.prefill_dispatch": "admit",
    "admit.extend": "admit", "admit.scatter_dispatch": "admit",
    "admit.first_token_sync": "admit", "admit.map_pages": "admit",
    "admit.prefill_chunk": "admit",
}


def _small_server(params, reg, paged):
    kw = (dict(paged=True, page_size=8) if paged
          else dict(prompt_buckets=(8, 16)))
    return DecodeServer(params, CFG, n_slots=2, max_len=64, round_len=4,
                        metrics=reg, **kw)


def _mixed_stream(rng, n):
    return [(rng.integers(0, CFG.vocab, (int(rng.integers(3, 30)),)),
             int(rng.integers(1, 9))) for _ in range(n)]


@pytest.mark.parametrize("paged", [False, True],
                         ids=["dense", "paged"])
def test_span_and_work_counters_balance(setup, paged):
    """After a whole run: one admission per request, every token is
    either a prefill's first token or a useful slot-step, every child
    stage's time fits inside its parent's, and each stage's ``_n`` is
    the number of times the stage ran: once a request in the paged
    scheduler and for a prompt past the widest bucket, once a LAUNCH
    for the dense scheduler's in-bucket groups."""
    from rlo_tpu.utils.metrics import Registry

    params = setup
    reg = Registry()
    srv = _small_server(params, reg, paged)
    reqs = _mixed_stream(np.random.default_rng(11), 5)
    for p, m in reqs:
        srv.submit(p, m)
    calls = 0
    while srv.has_work():
        srv.step_round()
        calls += 1
    c = reg.snapshot()["counters"]
    assert c["serve.admissions"] == len(reqs) == \
        c["serve.requests_completed"]
    assert c["serve.slot_steps_useful"] + c["serve.admissions"] == \
        c["serve.tokens_out"] == sum(m for _, m in reqs)
    assert c["serve.slot_steps"] == srv.steps_run * srv.n_slots
    assert c["serve.prefill_tokens"] == sum(len(p) for p, _ in reqs)
    assert c["serve.prefill_padded_tokens"] >= c["serve.prefill_tokens"]

    assert c["serve.step_round_n"] == c["serve.admit_n"] == calls
    for stage in ("round.dispatch", "round.wait", "round.readback",
                  "distribute"):
        assert c[f"serve.{stage}_n"] == c["serve.rounds"]
    if paged:
        assert c["serve.admit.first_token_sync_n"] == len(reqs)
        assert c["serve.admit.map_pages_n"] >= len(reqs)
        assert c["serve.admit.prefill_chunk_n"] == \
            c["serve.prefill_chunks"]
        assert c["serve.prefill_padded_tokens"] == \
            8 * c["serve.prefill_chunks"]
        assert c["serve.page_gauges_n"] == calls + c["serve.rounds"]
    else:
        n_ext = sum(-(-(len(p) - 16) // 16) for p, _ in reqs
                    if len(p) > 16)
        assert n_ext > 0      # the stream reaches past the last bucket
        assert c["serve.admit.extend_n"] == n_ext
        n_long = sum(len(p) > 16 for p, _ in reqs)
        assert c["serve.admit.scatter_dispatch_n"] == n_long
        assert c["serve.admit.batched_rows"] == len(reqs) - n_long
        # two slots: a launch admits one or two requests
        launches = c["serve.admit.launches"]
        assert (len(reqs) - n_long) / 2 <= launches <= len(reqs) - n_long
        for stage in ("stage_input", "prefill_dispatch",
                      "first_token_sync"):
            assert c[f"serve.admit.{stage}_n"] == launches + n_long
    for parent in ("step_round", "admit"):
        kids = sum(c.get(f"serve.{k}_ns", 0)
                   for k, par in SPAN_PARENT.items() if par == parent)
        assert 0 < kids <= c[f"serve.{parent}_ns"], parent
    assert "serve.tok_usec" not in reg.snapshot()["histograms"]
    assert "serve.occupancy_pct" not in reg.snapshot()["histograms"]


@pytest.mark.parametrize("mode", ["dense", "block", "paged"])
def test_build_log_names_the_stage_that_paid_for_each_program(setup,
                                                              mode):
    """A start as the build log holds it: every program JAX obtained
    for the server began under one of its spans (the constructor's
    among them) and is counted once in ``serve.build.*``, as many
    programs as an independent listener saw compiled; ``stats()``
    names the round's function and the admission's once each; the
    dense schedulers' one-row prefill is a trace of its own, a root
    under ``admit.prefill_dispatch`` before the loop's; and across ten
    further rounds nothing is built."""
    from rlo_tpu.utils.metrics import Registry
    from rlo_tpu.utils.tracing import BUILD_COUNTS, BUILDS

    params = setup
    cfg = (dataclasses.replace(CFG, block_len=4, mask_id=CFG.vocab - 1)
           if mode == "block" else CFG)
    compiles = []

    def listen(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    def wave(srv, rng):
        # whole rounds only: a paged round clipped to a shorter budget
        # is another program
        for _ in range(4):
            srv.submit(rng.integers(0, CFG.vocab, (int(rng.integers(3, 9)),)),
                       20 if mode == "block" else 21)
        while srv.has_work():
            srv.step_round()

    reg = Registry()
    rng = np.random.default_rng(21)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        kw = (dict(paged=True, page_size=8) if mode == "paged"
              else dict(prompt_buckets=(8, 16)))
        srv = DecodeServer(params, cfg, n_slots=2, max_len=64, round_len=4,
                           metrics=reg, **kw)
        wave(srv, rng)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    mine = [r for r in BUILDS.records if r.metrics is reg]
    assert mine and all(r.span.startswith("perf.serve.") for r in mine)
    c = reg.snapshot()["counters"]
    assert c["serve.init_n"] == 1 and c["serve.init_ns"] > 0
    build = {k: c["serve.build." + k] for k in BUILD_COUNTS}
    assert build["programs"] == len(compiles) > 0
    assert build == {k: sum(r.counts[k] for r in mine)
                     for k in BUILD_COUNTS}
    assert sum(r.t1 - r.t0 for r in mine) == sum(
        n for k, n in build.items() if k.endswith("_ns")) <= \
        c["serve.init_ns"] + c["serve.step_round_ns"]

    table = {row["fun_name"]: row for row in srv.stats()["build"]}
    admission = "chunk_fn" if mode == "paged" else "admit_rows"
    assert table["round_fn"]["calls"] == table[admission]["calls"] == 1
    assert table["round_fn"]["programs"] == 1
    assert table["round_fn"]["spans"] == ["perf.serve.round.dispatch"]
    stage = ("perf.serve.admit.prefill_chunk" if mode == "paged"
             else "perf.serve.admit.prefill_dispatch")
    assert table[admission]["spans"] == [stage]
    if mode != "paged":
        # the layers' trace, made where no other trace is open
        # (_launch_group): it is no program of its own, and the loop
        # that calls it is traced after it
        (one_row,) = [r for r in mine if r.fun_name == "prefill_slot"]
        assert (one_row.phase, one_row.span) == ("trace", stage)
        loop = next(r for r in mine if r.fun_name == "admit_rows")
        assert loop.phase == "trace" and one_row.t1 <= loop.t0

    rounds = c["serve.rounds"]
    wave(srv, rng), wave(srv, rng)
    c = reg.snapshot()["counters"]
    assert c["serve.rounds"] - rounds >= 10
    assert {k: c["serve.build." + k] for k in BUILD_COUNTS} == build
    assert c.get("serve.retraces", 0) == 0
    assert len([r for r in BUILDS.records if r.metrics is reg]) == \
        len(mine)


def test_profiler_trace_holds_nested_serve_spans(setup, tmp_path):
    """Under a jax.profiler session on the CPU two step_round() calls
    leave ``perf.serve.*`` events on ``/host:CPU`` that nest as
    SPAN_PARENT says; an admission stage carries the ``rid`` of its
    request (a prompt past the widest bucket) or the ``rows`` of its
    group (one launch a bucket), and between them they hold every
    request admitted."""
    import glob
    import warnings

    from jax.profiler import ProfileData
    from rlo_tpu.utils.metrics import Registry
    from rlo_tpu.utils.tracing import profile

    params = setup
    srv = _small_server(params, Registry(), paged=False)
    for p, m in _mixed_stream(np.random.default_rng(12), 4):
        srv.submit(p, m)
    with profile(str(tmp_path)):
        srv.step_round()
        srv.step_round()
    paths = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert len(paths) == 1
    events = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(paths[0]).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                events += [(e.name[len("perf.serve."):], e.start_ns,
                            e.start_ns + e.duration_ns, dict(e.stats))
                           for e in line.events
                           if e.name.startswith("perf.serve.")]
    by_stage = {}
    for name, a, b, stats in events:
        by_stage.setdefault(name, []).append((a, b, stats))
    assert len(by_stage["step_round"]) == 2
    assert len(by_stage["admit"]) == 2
    assert len(by_stage["round.wait"]) == 2
    admitted = set()
    for name, spans in by_stage.items():
        parent = SPAN_PARENT.get(name)
        for a, b, stats in spans:
            if name.startswith("admit."):
                assert ("rid" in stats) != ("rows" in stats), name
                if "rid" in stats:
                    admitted.add(int(stats["rid"]))
            if parent is not None:
                assert any(pa <= a and b <= pb
                           for pa, pb, _ in by_stage[parent]), name
    assert {"admit.stage_input", "admit.prefill_dispatch",
            "admit.scatter_dispatch", "admit.first_token_sync",
            "round.dispatch", "round.readback",
            "distribute"} <= set(by_stage)
    assert admitted and admitted <= {0, 1, 2, 3}
    for stage in ("stage_input", "prefill_dispatch", "first_token_sync"):
        spans = by_stage["admit." + stage]
        assert len(admitted) < sum(
            int(st.get("rows", 1)) for _, _, st in spans) <= 4, stage


@pytest.mark.parametrize("paged", [False, True],
                         ids=["dense", "paged"])
def test_submit_due_moves_latencies_by_the_offset(setup, paged,
                                                   monkeypatch):
    """``submit(due=)``: TTFT, queue wait and end-to-end latency count
    from the due time — with the server's clock held still, a request
    due 2.5 s ago reads exactly 2.5 s more than one without ``due``."""
    import types

    from rlo_tpu.models import serve as serve_mod
    from rlo_tpu.utils.metrics import Registry

    monkeypatch.setattr(serve_mod, "time", types.SimpleNamespace(
        perf_counter=lambda: 100.0))
    params = setup
    reg = Registry()
    srv = _small_server(params, reg, paged)
    rng = np.random.default_rng(13)
    srv.submit(rng.integers(0, CFG.vocab, (5,)), 3)
    srv.submit(rng.integers(0, CFG.vocab, (7,)), 3, due=100.0 - 2.5)
    srv.run()
    for name in ("serve.ttft_usec", "serve.queue_wait_usec",
                 "serve.e2e_usec"):
        assert sorted(reg.histogram(name).samples) == [0.0, 2.5e6], name


def test_retraces_counts_shapes_the_server_was_not_built_for(setup):
    """Admission never recompiles: a stream of prompt lengths across
    every bucket and past the last one leaves ``serve.retraces`` at 0.
    A clipped round has a new static ``kk``: that is a re-trace of
    ``_round`` and is counted under its name."""
    from rlo_tpu.utils.metrics import Registry

    params = setup
    reg = Registry()
    srv = DecodeServer(params, CFG, n_slots=2, max_len=96, round_len=4,
                       prompt_buckets=(8, 16, 32), metrics=reg)
    rng = np.random.default_rng(14)
    for plen in (3, 8, 9, 16, 20, 32, 40, 5):
        srv.submit(rng.integers(0, CFG.vocab, (plen,)), 6)
    srv.run()
    c = reg.snapshot()["counters"]
    assert c["serve.rounds"] >= 4
    assert c.get("serve.retraces", 0) == 0

    reg2 = Registry()
    clipped = DecodeServer(params, CFG, n_slots=2, max_len=96,
                           round_len=4, prompt_buckets=(8, 16, 32),
                           clip_rounds=True, metrics=reg2)
    clipped.submit(rng.integers(0, CFG.vocab, (5,)), 4)  # kk 3
    clipped.submit(rng.integers(0, CFG.vocab, (5,)), 7)  # kk 3, then 3
    clipped.submit(rng.integers(0, CFG.vocab, (5,)), 6)  # kk 1 or 2
    clipped.run()
    c2 = reg2.snapshot()["counters"]
    assert c2["serve.retraces"] == c2["serve.retraces._round"] >= 1
    assert not any(k.startswith("serve.retraces._")
                   and k != "serve.retraces._round" for k in c2)


# -- batched admission (PR 33): one launch and one sync a (pass, bucket)
# group; what the device computes for a request is its one-row prefill
ADMIT_CFGS = {
    "plain": lambda: CFG,
    "int8": lambda: dataclasses.replace(CFG, kv_cache_dtype="int8"),
    "latent": lambda: _latent_cfg(),
}


def _one_row_reference(params, cfg, buckets, max_len):
    """``reference(prompt, max_new, eos_id=None)``: a request's tokens by
    the plainest route — generate.prefill of its one padded row (its
    bucket's width; a prompt past the widest bucket whole, at its own
    length), then generate.decode_step a token at a time."""
    from rlo_tpu.models.generate import (_decode_cfg, decode_step,
                                         init_kv_cache, prefill)
    first = jax.jit(lambda p, t, n: prefill(
        p, t, init_kv_cache(cfg, 1, max_len), cfg, last_index=n - 1))
    step = jax.jit(lambda p, t, n, c: decode_step(p, t, n, c,
                                                  _decode_cfg(cfg)))

    def reference(prompt, max_new, eos_id=None):
        plen = len(prompt)
        width = plen if plen > buckets[-1] else _bucket(plen, buckets)
        padded = np.zeros((1, width), np.int32)
        padded[0, :plen] = prompt
        logits, cache = first(params, jnp.asarray(padded),
                              jnp.asarray([plen], jnp.int32))
        toks = [int(np.argmax(np.asarray(logits)[0]))]
        while len(toks) < max_new and toks[-1] != eos_id:
            logits, cache = step(
                params, jnp.asarray(toks[-1:], jnp.int32),
                jnp.asarray([plen + len(toks) - 1], jnp.int32), cache)
            toks.append(int(np.argmax(np.asarray(logits)[0])))
        return np.asarray(toks, np.int32)

    return reference


@pytest.mark.parametrize("entry", sorted(ADMIT_CFGS))
def test_batched_admission_matches_one_row_reference(entry):
    """A mixed queue through three slots: both buckets in one pass,
    more requests than free slots, one ``max_new == 1`` and one whose
    first token is its ``eos_id`` (both retire at admission: their
    slots are offered again in the same admission, a second launch),
    one prompt past the widest bucket (admitted alone). Every request's
    tokens equal the one-row reference's, and the counters say which
    path each took."""
    from rlo_tpu.utils.metrics import Registry
    cfg = ADMIT_CFGS[entry]()
    params = init_params(jax.random.PRNGKey(33), cfg)
    buckets, max_len = (8, 16), 64
    rng = np.random.default_rng(33)
    shape = [(5, 6), (12, 1), (7, 5), (21, 4), (16, 7), (3, 3), (9, 2),
             (8, 5)]                        # (prompt length, max_new)
    reqs = [[rng.integers(0, cfg.vocab, (plen,)), m, None]
            for plen, m in shape]
    reference = _one_row_reference(params, cfg, buckets, max_len)
    reqs[2][2] = int(reference(reqs[2][0], 1)[0])
    reg = Registry()
    srv = DecodeServer(params, cfg, n_slots=3, max_len=max_len,
                       round_len=4, prompt_buckets=buckets, metrics=reg)
    for prompt, max_new, eos in reqs:
        srv.submit(prompt, max_new, eos_id=eos)
    assert srv._admit() == 2        # requests 1 and 2 retired at once
    # pass 1: [0, 2] and [1]; pass 2: 3 alone (long) and [4]
    c = reg.snapshot()["counters"]
    assert c["serve.admit.launches"] == 3
    assert c["serve.admit.batched_rows"] == 4
    assert c["serve.admissions"] == 5
    assert srv.slot_ownership() == (0, 3, 4)
    outs = srv.run()
    assert len(outs[2]) == 1 and len(outs[1]) == 1
    for (prompt, max_new, eos), got in zip(reqs, outs):
        np.testing.assert_array_equal(got, reference(prompt, max_new, eos))
    c = reg.snapshot()["counters"]
    assert c["serve.admissions"] == len(reqs)
    assert c["serve.admit.batched_rows"] == len(reqs) - 1
    assert c["serve.admit.scatter_dispatch_n"] == 1     # the long one
    assert c["serve.admit.first_token_sync_n"] == \
        c["serve.admit.launches"] + 1
    assert c["serve.prefill_tokens"] == sum(p for p, _ in shape)
    assert c["serve.prefill_padded_tokens"] == \
        8 * 4 + 16 * 3 + (16 + 16)      # the long one: bucket + a chunk
    assert c.get("serve.retraces", 0) == 0
    assert reg.histogram("serve.ttft_usec").count == len(reqs) == \
        reg.histogram("serve.queue_wait_usec").count


def test_first_admission_traces_each_layer_once_a_bucket(setup,
                                                         monkeypatch):
    """The set-up cost of admission, without a chip: a fresh server's
    first admission of a two-bucket queue runs the layer body (python)
    ``n_layers`` times a bucket USED and not at all for the bucket no
    request needs; a second, differently sized pass traces nothing, and
    ``_jits`` holds one admission entry a bucket with no re-trace."""
    from rlo_tpu.models import generate as generate_mod
    from rlo_tpu.utils.metrics import Registry

    calls = []
    inner = generate_mod.apply_layer

    def counted(x, *a, **kw):
        calls.append(x.shape)
        return inner(x, *a, **kw)

    monkeypatch.setattr(generate_mod, "apply_layer", counted)
    params = setup
    reg = Registry()
    srv = DecodeServer(params, CFG, n_slots=4, max_len=96, round_len=4,
                       prompt_buckets=(8, 16, 32), metrics=reg)
    rng = np.random.default_rng(15)
    for plen in (3, 12, 8, 16):             # buckets 8 and 16, not 32
        srv.submit(rng.integers(0, CFG.vocab, (plen,)), 9)
    srv._admit()
    assert sorted(calls) == sorted(
        [(1, 8, CFG.d_model)] * CFG.n_layers
        + [(1, 16, CFG.d_model)] * CFG.n_layers)
    fn, built_for = srv._jits["_admit_rows"]
    assert built_for == 3 and fn._cache_size() == 2
    assert srv._jits["_prefill"][0]._cache_size() == 0
    srv.cancel(0), srv.cancel(2), srv.cancel(3)
    del calls[:]
    srv.submit(rng.integers(0, CFG.vocab, (5,)), 3)    # a group of one
    srv.submit(rng.integers(0, CFG.vocab, (9,)), 3)
    srv.submit(rng.integers(0, CFG.vocab, (16,)), 3)   # and one of two
    srv._admit()
    assert calls == [] and fn._cache_size() == 2
    srv.run()
    c = reg.snapshot()["counters"]
    assert c.get("serve.retraces", 0) == 0
    assert c["serve.admit.launches"] == 4
    assert c["serve.admit.batched_rows"] == c["serve.admissions"] == 7


def test_fabric_recorder_still_gets_prefill_chunk_spans(setup):
    """The one span helper hands a fabric-attached SpanRecorder the
    same ``Ev.SPAN`` the paged scheduler emitted before: stage
    PREFILL_CHUNK, the fabric rid in c/d, one per chunk."""
    from rlo_tpu.observe.spans import SpanRecorder, Stage
    from rlo_tpu.utils.metrics import Registry
    from rlo_tpu.utils.tracing import Ev, Tracer

    params = setup
    reg = Registry()
    srv = DecodeServer(params, CFG, n_slots=2, max_len=64, round_len=4,
                       paged=True, page_size=8, metrics=reg)
    tracer = Tracer(enabled=True)
    srv.spans = SpanRecorder(rank=3, clock=lambda: 0.0, tracer=tracer)
    srv.span_rid_of = {0: (7, 100), 1: None}.get  # rid 1: not fabric's
    rng = np.random.default_rng(15)
    srv.submit(rng.integers(0, CFG.vocab, (20,)), 3)   # 3 chunks of 8
    srv.submit(rng.integers(0, CFG.vocab, (9,)), 3)
    srv.run()
    spans = tracer.events(Ev.SPAN)
    assert len(spans) == 3
    for e in spans:
        assert (e.rank, e.a, e.c, e.d) == (3, int(Stage.PREFILL_CHUNK),
                                           100, 7)
        assert e.b >= 0 and e.ts_usec > 0
    assert reg.snapshot()["counters"]["serve.prefill_chunks"] == 5


# head_dim 64 so the decode kernel's shape gate accepts the cache
CFG_HD64 = TransformerConfig(vocab=64, d_model=64, n_heads=1, n_layers=1,
                             d_ff=64, dtype="float32")


@pytest.mark.parametrize("cache", ["tail", "int8"])
@pytest.mark.parametrize("near_edge", [-2, 0])
def test_attend_tile_counters_equal_the_hand_count(monkeypatch,
                                                   near_edge, cache):
    """``serve.attend_tiles`` / ``serve.attend_tiles_live`` against a
    count by hand from pos, kk and the kernel's exported tile width.
    With the write-behind tail every step of a round attends the cache
    as the round found it, positions < pos: tiles 0 .. (pos - 1) // bk
    of the n_k in the grid, the same in all kk steps (the round's own
    positions are in the tail). An int8 cache keeps the write of every
    step: step s attends positions <= pos + s, tiles
    0 .. (pos + s) // bk. Every slot counts, the never-admitted and
    the finished too (the kernel runs them; their pos advances with
    the round). The server is told it is on the tpu backend so that it
    counts at all; the model still attends through the einsum here,
    and the count needs only pos."""
    from rlo_tpu.models import serve as serve_mod
    from rlo_tpu.pallas.decode import (decode_lanes_fetched,
                                       decode_work_list,
                                       flash_decode_slots,
                                       flash_decode_tile)
    from rlo_tpu.utils.metrics import Registry

    monkeypatch.setattr(serve_mod, "_on_tpu", lambda: True)
    cfg = (CFG_HD64 if cache == "tail" else
           dataclasses.replace(CFG_HD64, kv_cache_dtype="int8"))
    max_len, kk, n_slots = 1024, 4, 3
    bk = flash_decode_tile(jax.ShapeDtypeStruct(
        (n_slots, 1, cfg.head_dim, max_len), jnp.float32), 1)
    n_k = max_len // bk
    assert n_k >= 2     # else there is no tile to skip at this size
    params = init_params(jax.random.PRNGKey(3), cfg)
    reg = Registry()
    srv = DecodeServer(params, cfg, n_slots=n_slots,
                       max_len=max_len, round_len=kk,
                       prompt_buckets=(8, 1024), metrics=reg)
    assert srv._attend_tiling == (bk, n_k)
    assert srv._kv_tail == (cache == "tail")
    rng = np.random.default_rng(26)
    # one short row; one whose context crosses (or starts on) the
    # first tile edge inside its first round; slot 2 stays free
    plens, outs = [5, bk + near_edge], [9, 6]
    for plen, out in zip(plens, outs):
        srv.submit(rng.integers(0, cfg.vocab, (plen,)), out)
    srv.run()
    rounds = srv.rounds_run
    assert rounds == 2      # 9 tokens: one at admission, 8 / kk rounds
    live = 0
    for slot_pos0 in plens + [0]:           # pos at the first round
        for step in range(rounds * kk):     # pos advances every step
            if cache == "tail":             # the cache at the round's start
                held = slot_pos0 + step // kk * kk - 1
            else:
                held = slot_pos0 + step
            live += min(max(held, 0) // bk, n_k - 1) + 1
    c = srv.stats()["counters"]
    assert c["serve.attend_tiles"] == rounds * kk * n_slots * n_k
    assert c["serve.attend_tiles_live"] == live
    # the kernel's grid is its work list: as many steps as live tiles
    # (a grid over max_len would have read == serve.attend_tiles), by
    # the list's own length for every step's positions
    assert c["serve.attend_steps"] == c["serve.attend_tiles_live"]
    steps = 0
    for step in range(rounds * kk):
        held = np.asarray(plens + [0]) + (
            step // kk * kk - 1 if cache == "tail" else step)
        steps += int(decode_work_list(jnp.asarray(held, jnp.int32), 1,
                                      bk, n_k)[2])
    assert c["serve.attend_steps"] == steps
    # what the kernel's copies move, by its own rule for every step's
    # positions: whole tiles before a row's last, that one granule-rounded;
    # between what the contexts hold and the live tiles whole
    fetched = lanes_live = 0
    for step in range(rounds * kk):
        held = np.asarray(plens + [0]) + (
            step // kk * kk - 1 if cache == "tail" else step)
        fetched += int(decode_lanes_fetched(held, 1, bk, max_len).sum())
        lanes_live += int(np.clip(held + 1, 0, max_len).sum())
    assert c["serve.attend_lanes_fetched"] == fetched
    assert c["serve.attend_lanes_live"] == lanes_live
    assert lanes_live <= fetched <= c["serve.attend_tiles_live"] * bk
    assert fetched % 128 == 0 and fetched < live * bk
    assert srv.stats()["gauges"]["serve.attend_fetch_depth"] == \
        flash_decode_slots(srv.cache[0]["k"], cfg.n_heads) >= 2
    # the short rows reach one tile a step. The long one reaches two
    # from the step its context passes the edge — or, with the tail,
    # from the first round that FINDS it past the edge: the second
    crossed = kk if cache == "tail" else rounds * kk - max(0, -near_edge)
    assert live == 3 * rounds * kk + crossed


def test_attend_tile_counters_absent_on_the_einsum_path(setup):
    """Off the tpu backend (here) decode_step attends through the
    einsum: nothing is tiled, so the server holds no tiling, never
    touches the three counters and stats() does not show them."""
    from rlo_tpu.utils.metrics import Registry

    reg = Registry()
    srv = DecodeServer(setup, CFG, n_slots=2, max_len=64, round_len=4,
                       prompt_buckets=(8, 16), metrics=reg)
    assert srv._attend_tiling is None
    srv.submit(np.arange(5), 6)
    srv.run()
    c = srv.stats()["counters"]
    assert c["serve.slot_steps"] > 0
    assert not any(k.startswith("serve.attend_") for k in c)
    # a shape the kernel's gate refuses holds none on the chip either
    srv._count_attend_tiles(4)
    assert not any(k.startswith("serve.attend_")
                   for kind in ("counters", "gauges")
                   for k in srv.stats()[kind])



# -- the write-behind tail (PR 28): a dense round keeps its new K/V rows
# in a token-major tail and folds them into the cache when it ends
def _latent_cfg():
    import json
    from pathlib import Path
    path = (Path(__file__).resolve().parent.parent / "perf" / "configs"
            / "deepseek-v3-ep16.json")
    model = json.loads(path.read_text())["tiny"]["model"]
    return TransformerConfig(**dict(model, dtype="float32",
                                    param_dtype="float32", n_layers=2))


TAIL_CFGS = {
    "mha": lambda: CFG,
    "gqa_rope": lambda: dataclasses.replace(CFG, n_kv_heads=2,
                                            pos_encoding="rope"),
    "latent": _latent_cfg,      # one latent row a token, expert layer
}


@pytest.mark.parametrize("name,round_len,clip", [
    ("mha", 1, False), ("mha", 5, False), ("mha", 5, True),
    ("mha", 32, False), ("gqa_rope", 5, False), ("latent", 8, False),
    ("latent", 32, True)])
def test_tail_rounds_match_dense_and_leave_the_per_step_cache(
        name, round_len, clip):
    """Token for token what per-request generate() yields — across
    round boundaries, slot reuse after retirement, clipped rounds, free
    and retired slots whose pos runs to max_len and past it — and after
    EVERY round the cache, pos and last_tok the per-step path leaves: a
    second server is held to the write of every step (as an int8 cache
    is) and fed the same requests. Layer 0's rows depend on the tokens
    alone, so they are equal to the bit (placement, and the columns
    dropped at max_len); deeper rows saw attends that sum the round's
    last positions in another order. The counters against their hand
    count."""
    from rlo_tpu.utils.metrics import Registry
    cfg = TAIL_CFGS[name]()
    params = init_params(jax.random.PRNGKey(5), cfg)
    n_slots, max_len = 2, 64

    def server(reg):
        return DecodeServer(params, cfg, n_slots=n_slots, max_len=max_len,
                            round_len=round_len, prompt_buckets=(8, 16),
                            clip_rounds=clip, metrics=reg)

    reg = Registry()
    srv, per_step = server(reg), server(Registry())
    assert srv._kv_tail and per_step._kv_tail
    per_step._kv_tail = False       # read when the round is traced
    rng = np.random.default_rng(28)
    reqs = [(rng.integers(0, cfg.vocab, (int(rng.integers(3, 15)),)),
             int(rng.integers(2, 21))) for _ in range(5)]
    reqs.append((rng.integers(0, cfg.vocab, (14,)), max_len - 14))
    for p, m in reqs:
        srv.submit(p, m)
        per_step.submit(p, m)
    rows = 0
    while srv.has_work():
        steps = srv.steps_run
        srv.step_round()
        per_step.step_round()
        rows += (srv.steps_run - steps) * n_slots
        np.testing.assert_array_equal(srv.pos, per_step.pos)
        np.testing.assert_array_equal(srv.last_tok, per_step.last_tok)
        for i, (got, want) in enumerate(zip(srv.cache, per_step.cache)):
            for key in want:
                if i == 0:
                    np.testing.assert_array_equal(got[key], want[key])
                else:
                    np.testing.assert_allclose(got[key], want[key],
                                               rtol=1e-5, atol=1e-5)
    if round_len >= 5 and not clip:     # a round ran past the cache's end
        assert srv.pos.max() >= max_len
    for (p, m), got in zip(reqs, srv.run()):
        np.testing.assert_array_equal(got, dense_oracle(params, cfg, p, m))
    c = reg.snapshot()["counters"]
    assert c["serve.kv_tail.rounds"] == c["serve.rounds"] == srv.rounds_run
    assert c["serve.kv_tail.rows"] == c["serve.slot_steps"] == rows
    tensors = 1 if cfg.mla else 2
    assert c["serve.kv_flush_blocks"] == (
        srv.rounds_run * 2 * n_slots * cfg.n_layers * tensors)
    assert not any(k.startswith("serve.kv_")
                   for k in per_step.stats()["counters"])


def test_int8_cache_keeps_the_write_of_every_step(setup):
    """Its scale sidecars have no tail: the round takes the per-step
    path (test_variants holds its tokens to generate) and the tail's
    counters stay untouched."""
    from rlo_tpu.models.generate import init_kv_cache, init_kv_tail
    from rlo_tpu.utils.metrics import Registry
    cfg = dataclasses.replace(CFG, kv_cache_dtype="int8")
    reg = Registry()
    srv = DecodeServer(init_params(jax.random.PRNGKey(3), cfg), cfg,
                       n_slots=2, max_len=64, round_len=3,
                       prompt_buckets=(8, 16), metrics=reg)
    assert not srv._kv_tail
    srv.submit(np.arange(5), 6)
    srv.run()
    assert not any(k.startswith("serve.kv_")
                   for k in reg.snapshot()["counters"])
    with pytest.raises(ValueError, match="no write-behind tail"):
        init_kv_tail(init_kv_cache(cfg, 2, 64), 4)
