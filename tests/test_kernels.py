"""Pallas kernel validation: interpret=True vs pure-jnp oracles, sweeping
shapes and dtypes (assignment requirement)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.event_engine import event_engine, event_engine_ref
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.selective_scan.kernel import selective_scan_kernel
from repro.kernels.selective_scan.ref import selective_scan_ref
from repro.kernels.st_cost import st_cost, st_cost_dense_ref, st_cost_ref
from repro.kernels.strategy_plan import strategy_plan
from repro.kernels.value_score import value_score, value_score_ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,KV,Sq,Skv,hd,causal,window,softcap",
    [
        (2, 4, 2, 128, 128, 64, True, None, None),
        (1, 4, 4, 256, 256, 32, True, None, 50.0),
        (2, 2, 1, 96, 192, 16, False, None, None),     # cross, GQA 2:1
        (1, 8, 4, 256, 256, 64, True, 64, None),       # sliding window
        (1, 2, 2, 64, 64, 128, True, None, None),
        (2, 6, 3, 80, 144, 32, True, None, None),      # ragged sizes (pad)
    ],
)
def test_flash_attention_matches_oracle(B, H, KV, Sq, Skv, hd, causal,
                                        window, softcap, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, KV, Skv, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, KV, Skv, hd)).astype(dtype)
    out = flash_attention_kernel(q, k, v, causal=causal, window=window,
                                 softcap=softcap, block_q=64, block_k=64,
                                 interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window,
                              softcap=softcap)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_attention_decode_mode():
    """q_offset + kv_len emulate one-token decode against a padded cache."""
    B, H, KV, hd, S = 1, 4, 2, 32, 128
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, 1, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, KV, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, KV, S, hd), jnp.float32)
    out = flash_attention_kernel(q, k, v, causal=True, q_offset=99,
                                 kv_len=100, block_q=8, block_k=64,
                                 interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True, q_offset=99, kv_len=100)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "Bz,S,Di,N,chunk,bd",
    [
        (2, 64, 32, 8, 16, 16),
        (1, 128, 64, 16, 32, 32),
        (2, 96, 48, 4, 32, 16),
        (1, 256, 128, 16, 64, 128),
    ],
)
def test_selective_scan_matches_oracle(Bz, S, Di, N, chunk, bd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    x = jax.random.normal(ks[0], (Bz, S, Di)).astype(dtype)
    dt = (jax.nn.softplus(jax.random.normal(ks[1], (Bz, S, Di))) * 0.1
          ).astype(dtype)
    B = jax.random.normal(ks[2], (Bz, S, N)).astype(dtype)
    C = jax.random.normal(ks[3], (Bz, S, N)).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[4], (Di, N)) * 0.3)
    D = jax.random.normal(ks[5], (Di,))
    h0 = jax.random.normal(ks[6], (Bz, Di, N))
    y1, h1 = selective_scan_kernel(x, dt, B, C, A, D, h0, chunk=chunk,
                                   block_d=bd, interpret=True)
    y2, h2 = selective_scan_ref(x, dt, B, C, A, D, h0)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32),
                               atol=TOL[dtype] * 10, rtol=TOL[dtype] * 10)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=TOL[dtype] * 10, rtol=TOL[dtype] * 10)


def _event_engine_case(seed, slots, links, levels):
    """Mixed slot-lifecycle flush inputs: ~1/4 released (all-hole path,
    zeroed state), ~1/3 freshly allocated (no cached rate — rem is read
    verbatim), the rest carried from a previous flush with finite
    (rate, eta)."""
    rng = np.random.default_rng(seed)
    path = np.where(rng.random((slots, levels)) < 0.35, -1,
                    rng.integers(0, links, (slots, levels)))
    path[:, 0] = rng.integers(0, links, slots)
    freed = rng.random(slots) < 0.25
    path[freed] = -1
    rem = rng.random(slots) * 1e9
    rate = rng.random(slots) * 1e7 + 1.0
    fresh = rng.random(slots) < 0.3
    rate[fresh | freed] = 0.0
    rem[freed] = 0.0
    eta = 321.5 + rng.random(slots) * 5e3
    eta[rate == 0.0] = np.inf
    bw = rng.random(links) * 1e8 + 1e5
    act = rng.integers(0, 12, links).astype(float)
    return path, rem, rate, eta, bw, act


@pytest.mark.parametrize("seed,slots,links,levels", [
    (0, 1, 4, 2),            # single transfer, two-level shape
    (1, 37, 23, 4),          # ragged (pads to lane/sublane multiples)
    (2, 256, 60, 5),         # deep 5-tier path shape
    (3, 1000, 500, 3),       # wide link space
])
def test_event_engine_interpret_matches_oracle(seed, slots, links, levels):
    """The fused event-engine flush kernel (share -> gather-min re-rate ->
    eta reconstruction -> running-min next completion) under x64
    interpret mode is *bit-identical* to the float64 numpy oracle: the
    batched engine's once-per-instant pass, whose runs
    golden_tolerance.json pins end-to-end."""
    path, rem, rate, eta, bw, act = _event_engine_case(seed, slots, links,
                                                       levels)
    ref = event_engine_ref(path, rem, rate, eta, bw, act, 321.5)
    out = event_engine(path, rem, rate, eta, bw, act, 321.5,
                       backend="interpret")
    for got, want in zip(out[:3], ref[:3]):
        assert np.array_equal(got, want)
    assert out[3] == ref[3]


def test_event_engine_all_released_returns_inf():
    """A flush over nothing but released slots rates everything to zero
    and reports no next completion (eta_min = inf)."""
    path = np.full((8, 3), -1, np.int64)
    z = np.zeros(8)
    eta = np.full(8, np.inf)
    bw = np.ones(4) * 1e6
    act = np.zeros(4)
    for backend in ("numpy", "interpret"):
        rem, rate, eta_new, eta_min = event_engine(
            path, z, z, eta, bw, act, 10.0, backend=backend)
        assert (rate == 0.0).all() and (rem == 0.0).all()
        assert np.isinf(eta_new).all() and np.isinf(eta_min)


@pytest.mark.parametrize("slots", [0, 1, 37, 64, 128, 129])
def test_event_engine_host_inputs_layout(slots):
    """The flush's inputs built in numpy on the host are the kernel's
    layout: the path transposed so slots ride the lanes, levels padded to 8 and slots to a multiple
    of 128 (one lane group at least) with -1 ids, then the int32 share
    ranks behind the padding's sentinel, whose table maps each rank back
    to its float64 share. The interpret route over them still matches
    the oracle."""
    from repro.kernels.event_engine.kernel import host_inputs
    links, levels = 23, 4
    path, rem, rate, eta, bw, act = _event_engine_case(slots, slots, links,
                                                       levels)
    act[:6] = [0.0, 1.0, 2.0, 2.0, 4.0, 4.0]
    bw[:6] = [1.25e8, 1.25e8, 2.5e8, 2.5e8, 5e8, 1.25e8]   # shares tie
    (path_t, ranks), table = host_inputs(path, bw, act)
    s_pad = max(128, -(-slots // 128) * 128)
    assert path_t.shape == (8, s_pad) and path_t.dtype == np.int32
    assert np.array_equal(path_t[:levels, :slots], path.T)
    assert (path_t[levels:] == -1).all() and (path_t[:, slots:] == -1).all()
    share = bw / np.maximum(1.0, act)
    assert ranks.dtype == np.int32 and ranks[0] == links
    assert np.array_equal(table[ranks[1:]], share)
    assert ranks[1] == ranks[2] == ranks[3] == ranks[4] != ranks[6]
    assert np.array_equal(np.argsort(ranks[1:], kind="stable"),
                          np.argsort(share, kind="stable"))
    assert table[links] == np.inf
    ref = event_engine_ref(path, rem, rate, eta, bw, act, 321.5)
    out = event_engine(path, rem, rate, eta, bw, act, 321.5,
                       backend="interpret")
    for got, want in zip(out, ref):
        assert np.array_equal(got, want)


@pytest.mark.parametrize("users,left_s", [(9, 3.7), (9, 12.3), (11, 3.7)])
def test_event_engine_kernel_route_keeps_float64_ties(users, left_s):
    """Two transfers that float64 ends at one instant still end at one
    instant on the kernel route, where a float32 flush split them: one
    alone on a 1000 Mbps link, one sharing another with ``users - 1``
    more, both ``left_s`` seconds from the end at their unchanged rates.
    The route's outputs equal the oracle's bit for bit in a process
    without x64; the float32 flush, computed here as a mutation, puts
    the two ends an ulp apart."""
    path = np.array([[0, -1], [1, -1]])
    bw, act = np.full(2, 1.25e8), np.array([1.0, users])
    rate = bw / act
    rem = np.zeros(2)
    eta = np.full(2, left_s)
    want = event_engine_ref(path, rem, rate, eta, bw, act, 0.0)
    assert want[2][0] == want[2][1]
    got = event_engine(path, rem, rate, eta, bw, act, 0.0,
                       backend="interpret")
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    f32 = np.float32
    rate32 = bw.astype(f32) / act.astype(f32)
    eta32 = rate.astype(f32) * eta.astype(f32) / rate32
    assert eta32[0] != eta32[1]


def test_event_engine_flush_crosses_once_each_way(monkeypatch):
    """A kernel-route flush makes no ``jax.device_put``: it dispatches one
    program whose operands are host numpy arrays, and that dispatch is
    its only host-to-device transfer (the guard allows transfers inside
    the program's call alone, so an eager op on host data anywhere else
    is refused). The program's output is held where the wrapper can only
    copy it once, and the result is bit-identical to the oracle."""
    import collections

    from jax._src import dispatch

    from repro.kernels.event_engine import kernel

    case = _event_engine_case(1, 37, 23, 4)
    want = event_engine_ref(*case, 321.5)
    event_engine(*case, 321.5, backend="interpret")   # compile uncounted
    counts = collections.Counter()

    class Fetched:
        def __init__(self, array):
            self.array = array

        def __array__(self, dtype=None, copy=None):
            counts["to_host"] += 1
            return np.asarray(self.array, dtype)

    real_impl, real_put = dispatch._batched_device_put_impl, jax.device_put
    real_call = kernel._flush_call

    def impl(*args, **params):
        counts["to_device"] += 1
        return real_impl(*args, **params)

    def put(*args, **kwargs):
        counts["device_put"] += 1
        return real_put(*args, **kwargs)

    def call(*operands, **kwargs):
        counts["programs"] += 1
        assert all(type(x) is np.ndarray for x in operands)
        with jax.transfer_guard_host_to_device("allow"):
            return Fetched(real_call(*operands, **kwargs))

    monkeypatch.setattr(dispatch, "_batched_device_put_impl", impl)
    monkeypatch.setattr(jax, "device_put", put)
    monkeypatch.setattr(kernel, "_flush_call", call)
    with jax.transfer_guard_host_to_device("disallow"):
        got = event_engine(*case, 321.5, backend="interpret")
    assert counts == {"programs": 1, "to_host": 1}
    for g, w in zip(got, want):
        assert np.array_equal(g, w)


@pytest.mark.parametrize("slots", [64, 200])
def test_event_engine_warm_flush_adds_no_compile(slots):
    """One flush at a slot capacity warms every later flush there: the
    route always hands the program numpy operands, so flushes of other
    slot states at that capacity add no entry to its jit cache (a call
    on device operands would add a second one). The least ranks it
    gives equal those of the same operands put on the device first."""
    from repro.kernels.event_engine.kernel import _flush_call, host_inputs
    cases = [_event_engine_case(seed, slots, 57, 2) for seed in range(4)]
    event_engine(*cases[0], 321.5, backend="interpret")
    warm = _flush_call._cache_size()
    for case in cases[1:]:
        got = event_engine(*case, 321.5, backend="interpret")
        for g, w in zip(got, event_engine_ref(*case, 321.5)):
            assert np.array_equal(g, w)
    assert _flush_call._cache_size() == warm
    path, _, _, _, bw, act = cases[-1]
    inputs, _ = host_inputs(path, bw, act)
    on_host = np.asarray(_flush_call(*inputs, interpret=True))
    put = np.asarray(_flush_call(*jax.device_put(inputs), interpret=True))
    assert on_host.dtype == put.dtype == np.int32
    assert np.array_equal(on_host, put)


def _value_score_case(seed, sites, files):
    """Random but realistic scorer inputs: sparse holders, bandwidths in
    the paper's LAN/WAN range, decayed-count-shaped demand."""
    rng = np.random.default_rng(seed)
    demand = rng.random((sites, files)) * 20.0
    sizes = rng.random(files) * 1e9 + 1e6
    presence = rng.random((sites, files)) < 0.25
    presence[0, :] = True                       # every file has a holder row
    bw = rng.random((sites, sites)) * 1.25e8 + 1e5
    return demand, sizes, presence, bw


@pytest.mark.parametrize("mode", ["cost", "plain"])
@pytest.mark.parametrize("seed,sites,files", [
    (0, 4, 8),               # tiny (heavy sublane/lane padding)
    (1, 13, 100),            # one paper region x the paper catalog
    (2, 52, 100),            # the full paper grid
    (3, 37, 260),            # ragged on both axes
])
def test_value_score_interpret_matches_oracle(seed, sites, files, mode):
    """The value-scoring kernel under x64 interpret mode is *bit-identical*
    to the float64 oracle (max/divide are exact IEEE ops and the
    max-reduction is order-independent) — the contract behind the
    ``econ='pallas-interpret'`` engine flag."""
    demand, sizes, presence, bw = _value_score_case(seed, sites, files)
    ref = value_score_ref(demand, sizes, presence, bw, mode=mode)
    out = value_score(demand, sizes, presence, bw, mode=mode,
                      backend="interpret")
    assert np.array_equal(out, ref)


def test_value_score_auto_backend_on_cpu_is_exact():
    demand, sizes, presence, bw = _value_score_case(7, 8, 24)
    ref = value_score_ref(demand, sizes, presence, bw)
    out = value_score(demand, sizes, presence, bw, backend="auto")
    assert np.array_equal(out, ref)


def test_value_score_self_supply_and_no_holder():
    """A file whose only holder is the destination itself scores its
    re-fetch-if-dropped value via *other* holders only; with no other
    holder it scores 0 (nothing to buy)."""
    demand = np.full((2, 2), 5.0)
    sizes = np.array([1e6, 1e6])
    presence = np.array([[True, True], [False, True]])
    bw = np.array([[10.0, 20.0], [30.0, 40.0]])
    v = value_score_ref(demand, sizes, presence, bw, mode="cost")
    assert v[0, 0] == 0.0                     # sole holder is site 0 itself
    assert v[0, 1] == pytest.approx(5.0 * 1e6 / 30.0)   # from site 1
    assert v[1, 0] == pytest.approx(5.0 * 1e6 / 20.0)   # from site 0
    plain = value_score_ref(demand, sizes, presence, bw, mode="plain")
    assert plain[0, 0] == 0.0 and plain[1, 0] == 5.0


def test_value_score_empty_and_errors():
    assert value_score_ref(np.zeros((0, 3)), np.ones(3),
                           np.zeros((0, 3), bool),
                           np.zeros((0, 0))).shape == (0, 3)
    with pytest.raises(ValueError, match="mode"):
        value_score_ref(np.zeros((1, 1)), np.ones(1),
                        np.ones((1, 1), bool), np.ones((1, 1)), mode="nope")
    with pytest.raises(ValueError, match="backend"):
        value_score(np.zeros((1, 1)), np.ones(1), np.ones((1, 1), bool),
                    np.ones((1, 1)), backend="cuda")


def _st_cost_case(seed, sites, files, jobs):
    """Random but realistic broker-batch inputs: sparse holders, some
    offline sites, durable-master fetchability, LAN/WAN-range bandwidths,
    12-ish-file requirement rows."""
    rng = np.random.default_rng(seed)
    bw = rng.random((sites, sites)) * 1.25e8 + 1e5
    presence = rng.random((sites, files)) < 0.2
    presence[0, :] = True                       # every file has a holder row
    online = rng.random(sites) < 0.85
    online[0] = True
    fetch_mask = presence & online[:, None]
    fetch_mask[0, :] = presence[0, :]           # site 0 plays durable master
    sizes = rng.random(files) * 1e9 + 1e6
    required = rng.random((jobs, files)) < min(0.5, 12.0 / files)
    rel = rng.random(sites) * 50.0
    return bw, fetch_mask, presence, sizes, required, rel, online


@pytest.mark.parametrize("seed,sites,files,jobs", [
    (0, 4, 8, 3),            # tiny (heavy sublane/lane padding)
    (1, 13, 100, 17),        # one paper region x the paper catalog
    (2, 52, 100, 50),        # the full paper grid x a bulk burst
    (3, 37, 260, 9),         # ragged on every axis
])
def test_st_cost_interpret_matches_oracle(seed, sites, files, jobs):
    """The blocked st-cost kernel under x64 interpret mode is
    *bit-identical* to the float64 oracle: the holder max is
    order-independent, max/divide are exact IEEE ops, and the file sum
    runs sequentially over ascending file index in both."""
    case = _st_cost_case(seed, sites, files, jobs)
    ref = st_cost_ref(*case)
    out = st_cost(*case, backend="interpret")
    assert np.array_equal(out, ref)


@pytest.mark.parametrize("seed,sites,files,jobs", [
    (0, 4, 8, 3), (2, 52, 100, 50), (3, 37, 260, 9),
])
def test_st_cost_blocked_matches_dense(seed, sites, files, jobs):
    """The blocked pass equals the pre-blocked dense reduction (the
    ``(sites, files, sites)`` broadcast the old broker materialized) bit
    for bit — skipping exact-zero terms of a nonnegative running sum and
    reordering an exact max change nothing."""
    case = _st_cost_case(seed, sites, files, jobs)
    assert np.array_equal(st_cost_ref(*case), st_cost_dense_ref(*case))


def test_st_cost_auto_backend_on_cpu_is_exact():
    """backend='auto' off-TPU routes to the float64 oracle — the fast
    path the jitted shortesttransfer broker uses per dispatch batch."""
    case = _st_cost_case(7, 8, 24, 5)
    assert np.array_equal(st_cost(*case, backend="auto"),
                          st_cost_ref(*case))


def test_st_cost_guards_and_edges():
    """Zero-bandwidth guard (missing file with no fetchable source costs
    inf), offline sites cost inf, empty batches and empty catalogs work."""
    bw = np.array([[5.0, 5.0], [5.0, 5.0]])
    presence = np.array([[True], [False]])
    fetch = np.zeros((2, 1), bool)              # nothing fetchable at all
    sizes = np.array([10.0])
    required = np.array([[True]])
    rel = np.array([0.25, 0.5])
    online = np.array([True, False])
    out = st_cost_ref(bw, fetch, presence, sizes, required, rel, online)
    assert out[0, 0] == 0.25                    # present locally: queue only
    assert out[0, 1] == np.inf                  # offline
    fetch = np.array([[True], [False]])
    out = st_cost_ref(bw, fetch, presence, sizes, required, rel,
                      np.array([True, True]))
    assert out[0, 1] == max(10.0 / 5.0, 0.5)    # fetched from site 0
    assert st_cost_ref(bw, fetch, presence, sizes,
                       np.zeros((0, 1), bool), rel,
                       online).shape == (0, 2)
    empty_args = (bw, np.zeros((2, 0), bool), np.zeros((2, 0), bool),
                  np.zeros(0), np.zeros((3, 0), bool), rel,
                  np.array([True, False]))
    empty = st_cost_ref(*empty_args)
    assert np.array_equal(empty, [[0.25, np.inf]] * 3)  # queue time only
    # the kernel route must survive a 0-wide file axis too (empty batch
    # union / empty catalog), bit-identically
    assert np.array_equal(st_cost(*empty_args, backend="interpret"), empty)
    with pytest.raises(ValueError, match="backend"):
        st_cost(bw, fetch, presence, sizes, required, rel, online,
                backend="cuda")


def _collect_avals(jaxpr, out):
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.append(aval)
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    _collect_avals(inner, out)
                elif hasattr(sub, "eqns"):
                    _collect_avals(sub, out)
    return out


def test_st_cost_kernel_never_materializes_rank3():
    """Shape guard on the blocked path: abstract evaluation of the whole
    kernel call (padding, pallas_call body, fori loops) must contain no
    rank-3 intermediate — the ``(sites, files, sites)`` /
    ``(jobs, files, sites)`` broadcasts are exactly what this kernel
    exists to avoid — and no buffer larger than the padded 2-D planes."""
    from repro.kernels.st_cost.kernel import st_cost_kernel
    sites, files, jobs = 52, 100, 50
    case = _st_cost_case(2, sites, files, jobs)
    bw, fetch_mask, presence, sizes, required, rel, online = [
        np.asarray(a, np.float32) for a in case]
    jaxpr = jax.make_jaxpr(
        lambda *a: st_cost_kernel(*a, interpret=True))(
            bw, fetch_mask, presence, sizes, required, rel, online)
    avals = _collect_avals(jaxpr.jaxpr, [])
    assert avals, "no intermediates collected — walker is broken"
    pad = 128
    plane = max((sites + pad) * (files + pad), (jobs + pad) * (sites + pad))
    for aval in avals:
        assert len(aval.shape) <= 2, f"rank-3 intermediate: {aval}"
        assert int(np.prod(aval.shape, dtype=np.int64)) <= plane, aval


def test_selective_scan_streaming_equivalence():
    """Scanning a sequence in two kernel calls (carrying h) == one call."""
    Bz, S, Di, N = 1, 64, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    x = jax.random.normal(ks[0], (Bz, S, Di), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bz, S, Di))) * 0.1
    B = jax.random.normal(ks[2], (Bz, S, N))
    C = jax.random.normal(ks[3], (Bz, S, N))
    A = -jnp.exp(jax.random.normal(ks[4], (Di, N)) * 0.3)
    D = jax.random.normal(ks[5], (Di,))
    h0 = jnp.zeros((Bz, Di, N))
    y_full, h_full = selective_scan_ref(x, dt, B, C, A, D, h0)
    half = S // 2
    y1, h_mid = selective_scan_ref(x[:, :half], dt[:, :half], B[:, :half],
                                   C[:, :half], A, D, h0)
    y2, h_end = selective_scan_ref(x[:, half:], dt[:, half:], B[:, half:],
                                   C[:, half:], A, D, h_mid)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_end), np.asarray(h_full),
                               atol=1e-5)


# -- strategy_plan: batched replica-strategy planning ----------------------

def _strategy_plan_case(seed, sites, pairs):
    """Random burst: forced holder per pair (masters are durable), block
    regions, half the sites carrying decayed serve load."""
    rng = np.random.default_rng(seed)
    bw = rng.random((sites, pairs)) * 1.25e8 + 1e5
    fetch = rng.random((sites, pairs)) < 0.15
    fetch[rng.integers(0, sites, pairs), np.arange(pairs)] = True
    n_regions = max(2, sites // 8)
    region = np.arange(sites) * n_regions // sites
    local = region[:, None] == rng.integers(0, n_regions, pairs)[None, :]
    serve = np.where(rng.random(sites) < 0.5, rng.random(sites) * 9.0, 0.0)
    size = rng.random(pairs) * 1e9 + 1e6
    free = np.where(rng.random(pairs) < 0.5,
                    rng.random(pairs) * 2e9, rng.random(pairs) * 1e8)
    return bw, fetch, local, serve, free, size


@pytest.mark.parametrize("seed,sites,pairs", [
    (0, 4, 3),              # tiny (heavy sublane/lane padding)
    (1, 13, 17),            # one paper region
    (2, 52, 50),            # the full paper grid x a bulk burst
    (3, 129, 50),           # ragged site axis, grid_500-burst pair count
    (4, 37, 260),           # ragged on both axes
])
def test_strategy_plan_interpret_matches_oracle(seed, sites, pairs):
    """The plan kernel under x64 interpret mode is *bit-identical* to the
    float64 oracle: where/divide/compare are exact IEEE ops and the
    strict-> running maximum is np.argmax's first occurrence."""
    case = _strategy_plan_case(seed, sites, pairs)
    ref = strategy_plan(*case, backend="numpy")
    out = strategy_plan(*case, backend="interpret")
    for got, want in zip(out, ref):
        assert np.array_equal(got, want)


def test_strategy_plan_auto_backend_on_cpu_is_exact():
    """backend='auto' off-TPU routes to the float64 oracle — the per-burst
    fast path ``strategy_mode="batch"`` uses."""
    case = _strategy_plan_case(7, 24, 9)
    ref = strategy_plan(*case, backend="numpy")
    out = strategy_plan(*case, backend="auto")
    for got, want in zip(out, ref):
        assert np.array_equal(got, want)


def test_strategy_plan_decisions_and_edges():
    """Hand-checkable burst: lowest-id tie-break, serve-load discount
    flipping a pick, region-local restriction, inter-region flag off the
    chosen row, store verdict, empty-burst shapes."""
    bw = np.array([[4.0, 8.0], [4.0, 2.0], [3.0, 9.0]])
    fetch = np.array([[True, True], [True, True], [False, True]])
    local = np.array([[False, False], [True, True], [True, False]])
    serve = np.zeros(3)
    free = np.array([5.0, 1.0])
    size = np.array([4.0, 2.0])
    src_g, src_l, has_l, inter_g, store_ok = strategy_plan(
        bw, fetch, local, serve, free, size, backend="numpy")
    assert list(src_g) == [0, 2]        # pair 0: 4.0 tie -> lowest id
    assert list(src_l) == [1, 1]        # region-restricted best
    assert list(has_l) == [True, True]
    assert list(inter_g) == [True, True]
    assert list(store_ok) == [True, False]
    # a serve load on site 2 flips pair 1's global pick to site 0
    src_g2, _, _, inter_g2, _ = strategy_plan(
        bw, fetch, local, np.array([0.0, 0.0, 1.0]), free, size,
        backend="numpy")
    assert list(src_g2) == [0, 0]
    assert list(inter_g2) == [True, True]
    # empty burst: all five outputs are 0-wide
    empty = strategy_plan(bw[:, :0], fetch[:, :0], local[:, :0], serve,
                          free[:0], size[:0], backend="numpy")
    assert all(o.shape == (0,) for o in empty)
    with pytest.raises(ValueError, match="backend"):
        strategy_plan(bw, fetch, local, serve, free, size, backend="bogus")
