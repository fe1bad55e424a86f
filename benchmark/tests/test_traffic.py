"""The general traffic generator: the same seed gives the same requests,
templates fill, rates come from the cell's file, arrivals have the rate
asked for."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import traffic  # noqa: E402

RECOMMEND = {"weight": 1.0, "method": "GET",
             "path": "/recommend/{user}?howMany=10",
             "expect": {"status": 200, "json_list_len": 10},
             "check": "recommend"}
OPEN = {"loop": "open", "connections": 4,
        "arrivals": {"process": "poisson", "rate_qps": 200.0},
        "endpoints": [RECOMMEND]}
CLOSED = {"loop": "closed", "clients": 2, "endpoints": [RECOMMEND]}


def _wire(plan, n):
    return [plan.request(i)[0] for i in range(n)]


def test_same_seed_same_requests_other_seed_other_requests():
    a = traffic.Plan(OPEN, 7, 5.0, 1000, 5000, 200.0)
    b = traffic.Plan(OPEN, 7, 5.0, 1000, 5000, 200.0)
    c = traffic.Plan(OPEN, 8, 5.0, 1000, 5000, 200.0)
    assert np.array_equal(a.due, b.due)
    assert _wire(a, 50) == _wire(b, 50)
    assert _wire(a, 50) != _wire(c, 50)


def test_closed_loop_draws_as_it_goes():
    plan = traffic.Plan(CLOSED, 1, 5.0, 100, 100, None)
    assert plan.due is None and plan.connections == 2
    far = plan.request(20000)[0]           # past several chunks
    again = traffic.Plan(CLOSED, 1, 5.0, 100, 100, None)
    assert again.request(20000)[0] == far
    assert far.startswith(b"GET /recommend/") and far.endswith(b"\r\n\r\n")


def test_poisson_arrivals_have_the_rate_and_stay_inside():
    rng = np.random.default_rng(3)
    t = traffic.arrival_times({"process": "poisson"}, 500.0, 20.0, rng)
    assert t.min() >= 0 and t.max() < 20.0 and np.all(np.diff(t) >= 0)
    assert abs(len(t) / 20.0 - 500.0) < 25.0     # 5 sigma of Poisson


def test_bursty_arrivals_keep_the_mean_and_are_silent_between():
    rng = np.random.default_rng(3)
    t = traffic.arrival_times(
        {"process": "bursty", "duty": 0.25, "period_s": 2.0},
        400.0, 40.0, rng)
    assert abs(len(t) / 40.0 - 400.0) < 25.0
    assert np.all(np.mod(t, 2.0) < 0.5 + 1e-9)   # only in the on-phase


def test_templates_methods_and_bodies():
    mix = {"loop": "closed", "clients": 1, "endpoints": [
        {"method": "POST", "path": "/pref/{user}/{item}", "body": "2.5",
         "expect": {"status": 200}},
    ]}
    wire, ep, user, _ = traffic.Plan(mix, 1, 1.0, 50, 60, None).request(0)
    head, body = wire.split(b"\r\n\r\n")
    assert head.startswith(b"POST /pref/%d/" % user)
    assert b"Content-Length: 3" in head and body == b"2.5"
    many = {"loop": "closed", "clients": 1, "endpoints": [
        {"path": "/estimate/{user}/{items:3}",
         "expect": {"status": 200, "json_list_len": 3}}]}
    wire, ep, user, _ = traffic.Plan(many, 1, 1.0, 50, 60, None).request(0)
    path = wire.split(b" ")[1].decode()
    assert len(path.split("/")) == 6 and ep.list_len == 3


def test_weights_choose_endpoints_and_zipf_skews_users():
    mix = {"loop": "closed", "clients": 1,
           "users": {"pick": "zipf", "a": 1.2},
           "endpoints": [dict(RECOMMEND, weight=3.0),
                         {"weight": 1.0, "path": "/knownItems/{user}"}]}
    plan = traffic.Plan(mix, 5, 1.0, 1000, 1000, None)
    reqs = [plan.request(i) for i in range(4000)]
    share = sum(r[0].startswith(b"GET /recommend") for r in reqs) / 4000
    assert 0.70 < share < 0.80
    users = [r[2] for r in reqs]
    assert users.count(0) > 10 * max(1, users.count(500))


def test_one_request_in_sixteen_is_sampled():
    plan = traffic.Plan(CLOSED, 2, 1.0, 100, 100, None, sample_every=16)
    hits = sum(plan.request(i)[3] for i in range(16000))
    assert 800 < hits < 1200


def test_rate_comes_from_the_mix_or_the_cells_file():
    assert traffic.offered_rate(CLOSED, {}) is None
    assert traffic.offered_rate(OPEN, {}) == 200.0
    from_cell = {"loop": "open", "arrivals": {
        "rate_qps": {"cell": "knee_qps", "times": 0.8}}}
    assert traffic.offered_rate(from_cell, {"knee_qps": 1000}) == 800.0
    with pytest.raises(traffic.TrafficError):
        traffic.offered_rate(from_cell, {})
    with pytest.raises(traffic.TrafficError):
        traffic.Plan(dict(OPEN, loop="sideways"), 1, 1.0, 10, 10, 1.0)
