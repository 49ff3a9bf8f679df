"""The trace reduction on a hand-built trace."""

import pytest

from harness import trace as tr


def _trace():
    # device 0: busy 0-4, 5-7 (a collective 3-6 of which 4-5 is exposed),
    # then 9-10; device 1: busy 0-10
    d0 = [("fusion.1", 0.0, 4.0, {"long_name": "%fusion.1 = bf16[48,128]{1,0} fusion(...)"}),
          ("all-reduce.2", 3.0, 3.0, {}),
          ("fusion.7", 5.0, 2.0, {"long_name": "%fusion.7 = bf16[48,128]{1,0} fusion(...)"}),
          ("copy.3", 9.0, 1.0, {})]
    d1 = [("fusion.1", 0.0, 10.0, {})]
    host = [("bench.engine_step", 6.5, 2.0, {}), ("bench.issue", 8.6, 0.2, {})]
    return {"devices": {0: {"ops": d0, "modules": [
                ("jit_pir_eval_serving_decode(1)", 0.0, 4.0, {}),
                ("jit_pir_eval_serving_prefill_b64(2)", 5.0, 2.0, {}),
                ("jit_pir_eval_serving_decode(1)", 9.0, 1.0, {})]},
                        1: {"ops": d1, "modules": []}},
            "host": host}


def test_union_and_idle_share():
    assert tr.union_seconds([(0, 4), (3, 3), (5, 2), (9, 1)]) == 8.0
    busy, window = tr.busy_and_window(_trace())
    assert window == 10.0
    assert busy == pytest.approx((8.0 + 10.0) / 2)   # averaged over chips
    assert 1 - busy / window == pytest.approx(0.1)


def test_exposed_collective_time():
    t = _trace()
    # the all-reduce runs 3-6; compute covers 3-4 and 5-6: 4-5 is exposed
    assert tr.exposed_collective_seconds(t["devices"][0]["ops"]) == \
        pytest.approx(1.0)
    assert tr.exposed_collective_seconds(t["devices"][1]["ops"]) == 0.0


def test_top_ops_group_by_name_and_shape():
    rows = dict(tr.top_ops(_trace()))
    assert rows["fusion bf16[48,128]"] == pytest.approx(3.0)   # (4+2)/2 chips
    assert rows["fusion"] == pytest.approx(5.0)
    assert rows["all-reduce"] == pytest.approx(1.5)


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    rows = tr.idle_gaps_by_span(_trace())
    assert len(rows) == 1                  # one gap on device 0: 7 to 9
    label, secs = rows[0]
    assert label.startswith("bench.engine_step") and secs == pytest.approx(2.0)


def test_module_durations_by_name():
    t = _trace()
    assert tr.module_durations(t, "serving[._]decode") == [4.0, 1.0]
    assert tr.module_durations(t, "serving[._]prefill") == [2.0]
    assert tr.op_seconds(t, "^all-reduce") == pytest.approx(1.5)
