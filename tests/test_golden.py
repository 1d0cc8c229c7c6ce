"""Golden wire corpus: every endpoint's canonical bytes, locked on disk.

``tests/golden/`` holds the exact request/response bytes for each
endpoint envelope -- ``/v1/query``, ``/v1/query_many``, ``/v1/route``,
the structured error shape, and the ``/v1/metrics`` JSON rendering. The
builders below reconstruct each envelope from fixed values; the test
asserts the encoder still produces the committed bytes. Any diff here is
a WIRE-BREAKING change: old clients will see different bytes. If the
break is intentional, bump ``WIRE_VERSION``, regenerate with

    REPRO_UPDATE_GOLDEN=1 pytest tests/test_golden.py

and say so loudly in the changelog. Decoders are additionally checked as
exact inverses over the corpus (decode . encode == identity), so the
corpus doubles as a decoder regression net.
"""

import os
import pathlib

import numpy as np
import pytest

from repro.obs.metrics import Registry
from repro.service import wire
from repro.service.portfolio import RouteRequest, RouteResponse
from repro.service.query import QueryRequest, QueryResponse

GOLDEN_DIR = pathlib.Path(__file__).parent / "golden"
UPDATE = os.environ.get("REPRO_UPDATE_GOLDEN") == "1"


# ---------------------------------------------------------------------------
# fixed envelope builders (pure values -> bytes; no sweeps, no clocks)
# ---------------------------------------------------------------------------


def _query_request() -> bytes:
    return wire.encode_request(
        QueryRequest(
            freqs={"heat2d": 2.0, "jacobi2d": 1.0},
            max_area=450.0,
            min_area=60.0,
            top_k=3,
            pareto=True,
            fix={"n_sm": 16.0},
        ),
        artifact="0123456789abcdef0123",
        route={"gpu": "titanx", "workload": "paper-8-2048"},
        deadline_ms=250.0,
    )


def _query_many_request() -> bytes:
    return wire.encode_request_many(
        [
            (QueryRequest(freqs={"heat2d": 1.0}), None, {"gpu": "gtx980"}),
            (QueryRequest(max_area=650.0, top_k=2), "0123456789abcdef0123", None),
        ]
    )


def _route_request() -> bytes:
    return wire.encode_route_request(
        RouteRequest(cell="llama3-8b:decode"),
        artifact="fedcba98765432100123",
        route={"gpu": "tpu_v5e"},
        deadline_ms=100.0,
    )


def _query_response() -> bytes:
    # exercises the $f non-finite tagging (infeasible -> -inf gflops)
    # alongside a normal answer's full field surface
    return wire.encode_response(
        QueryResponse(
            artifact_key="0123456789abcdef0123",
            best_index=7,
            best_gflops=1063.25,
            best_weighted_time=7.0625,
            best_point={"area": 61.5, "m_sm": 432.0, "n_sm": 2.0, "n_v": 320.0},
            top_k=[
                {"area": 61.5, "gflops": 1063.25, "index": 7.0},
                {"area": 80.0, "gflops": 990.5, "index": 12.0},
            ],
            pareto_indices=np.array([2, 7, 12], np.int64),
            baseline_best_index=3,
            baseline_best_gflops=-np.inf,
            cached=True,
            batch_size=4,
        )
    )


def _query_many_response() -> bytes:
    ok = QueryResponse(
        artifact_key="0123456789abcdef0123",
        best_index=-1,
        best_gflops=-np.inf,
        best_weighted_time=np.inf,
        best_point={},
        top_k=[],
    )
    return wire.encode_response_many(
        [ok, ("unknown_artifact", "no artifact matches selector {'gpu': 'rtx'}")]
    )


def _route_response() -> bytes:
    return wire.encode_route_response(
        RouteResponse(
            portfolio_key="fedcba98765432100123",
            sweep_key="0123456789abcdef0123",
            cell="heat2d",
            cell_indices=(0, 6, 12),
            hw_index=42,
            member_slot=1,
            point={"area": 61.5, "m_sm": 432.0, "n_sm": 2.0, "n_v": 320.0},
            time_s=7.0625,
            gflops=1063.25,
            degraded=True,
            fallback_from=(17,),
        )
    )


def _error() -> bytes:
    return wire.encode_error(
        "portfolio_exhausted", "every member design failed for cell 'heat2d'"
    )


def _metrics_json() -> bytes:
    # a private registry with one of each family kind and fixed
    # observations: the canonical /v1/metrics?format=json rendering
    reg = Registry()
    c = reg.counter("repro_requests_total", "requests", labels=("endpoint",))
    c.labels(endpoint="/v1/route").inc(3)
    c.labels(endpoint="/v1/query").inc(5)
    g = reg.gauge("repro_pool_servers", "resident servers")
    g.set(2)
    h = reg.histogram("repro_route_seconds", "route latency",
                      buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.002, 0.05):
        h.observe(v)
    return reg.render_json()


def _slo_json() -> bytes:
    # a fixed-clock SLOTracker fed a fixed request mix: the canonical
    # /v1/slo?format=json rendering (burn rates, latency estimates,
    # per-route status), no wall clock anywhere
    from repro.obs.slo import SLOTracker

    t = [0.0]
    tracker = SLOTracker(clock=lambda: t[0])
    for i in range(20):
        t[0] = float(i)
        tracker.record("/v1/query", 0.004 + 0.001 * (i % 3), ok=True)
        tracker.record("/v1/route", 0.002, ok=(i % 10 != 0))
    t[0] = 30.0
    tracker.record("/v1/query", 0.250, ok=False)  # one slow 5xx outlier
    return wire.encode_slo_response(tracker.report(now=30.0))


CORPUS = {
    "query_request.json": _query_request,
    "query_many_request.json": _query_many_request,
    "route_request.json": _route_request,
    "query_response.json": _query_response,
    "query_many_response.json": _query_many_response,
    "route_response.json": _route_response,
    "error.json": _error,
    "metrics.json": _metrics_json,
    "slo.json": _slo_json,
}


@pytest.mark.parametrize("name", sorted(CORPUS))
def test_golden_bytes_stable(name):
    got = CORPUS[name]()
    path = GOLDEN_DIR / name
    if UPDATE:
        GOLDEN_DIR.mkdir(exist_ok=True)
        path.write_bytes(got)
    assert path.exists(), (
        f"missing golden file {path}; generate with REPRO_UPDATE_GOLDEN=1"
    )
    want = path.read_bytes()
    assert got == want, (
        f"{name}: wire bytes changed -- this breaks deployed clients. "
        "If intentional, bump WIRE_VERSION and regenerate the corpus "
        "(REPRO_UPDATE_GOLDEN=1)."
    )


def test_golden_decoders_invert_corpus():
    """decode(encode(x)) == x over the committed bytes (not just today's
    encoder output), so decoder drift is caught even when encoders hold."""
    req, artifact, route, deadline = wire.decode_route_request_full(
        (GOLDEN_DIR / "route_request.json").read_bytes()
    )
    assert req == RouteRequest(cell="llama3-8b:decode")
    assert artifact == "fedcba98765432100123"
    assert route == {"gpu": "tpu_v5e"} and deadline == 100.0

    resp = wire.decode_route_response(
        (GOLDEN_DIR / "route_response.json").read_bytes()
    )
    assert resp.degraded and resp.fallback_from == (17,)
    assert wire.encode_route_response(resp) == (
        GOLDEN_DIR / "route_response.json"
    ).read_bytes()

    q = wire.decode_response((GOLDEN_DIR / "query_response.json").read_bytes())
    assert q.baseline_best_gflops == -np.inf  # $f tag round-trips
    assert wire.encode_response(q) == (
        GOLDEN_DIR / "query_response.json"
    ).read_bytes()

    many = wire.decode_response_many(
        (GOLDEN_DIR / "query_many_response.json").read_bytes()
    )
    assert isinstance(many[0], QueryResponse)
    assert isinstance(many[1], wire.RemoteError)
    assert many[1].code == "unknown_artifact" and many[1].http_status == 404

    qreq, art, rt = wire.decode_request(
        (GOLDEN_DIR / "query_request.json").read_bytes()
    )
    assert art == "0123456789abcdef0123" and rt["gpu"] == "titanx"
    assert qreq.top_k == 3 and qreq.fix == {"n_sm": 16.0}

    with pytest.raises(wire.RemoteError) as exc:
        wire.decode_route_response((GOLDEN_DIR / "error.json").read_bytes(),
                                   http_status=503)
    assert exc.value.code == "portfolio_exhausted"
