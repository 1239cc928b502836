"""``adm_per_s`` counts a pass's reservations as ``passes`` counts the
pass: on a hand-made record whose last pass straddles the window's end,
the value moves by the part and never by a step."""

import types

import pytest

from benchmark import endtoend


def record(end: float):
    """Three passes: [0, 2] seats 10, [3, 5] seats 10 (one of them
    preempted away at 4.5, one more after the window's end), and a
    closing pass [8, 12] that stamps 100 reservations inside one commit
    at 10.0-10.1 s. The window is [0, end]."""
    passes = [{"t_start": 0.0, "t_end": 2.0},
              {"t_start": 3.0, "t_end": 5.0},
              {"t_start": 8.0, "t_end": 12.0}]
    res = [(f"a{i}", 1.0 + i / 100) for i in range(10)]
    res += [(f"b{i}", 4.0 + i / 100) for i in range(10)]
    res += [(f"c{i}", 10.0 + i / 1000) for i in range(100)]
    ev = [("b3", 4.5, 4.03), ("b4", 11.5, 4.04)]
    replay = types.SimpleNamespace(
        passes=passes, reservations=res, evictions=ev, arrivals=[],
        start_at=0.0)
    win = {"t0": 0.0, "t_end": end}
    return replay, win, endtoend.pass_parts(passes, end)


def adm(end: float) -> tuple[float, float]:
    replay, win, parts = record(end)
    out, _window, info = endtoend.measure(replay, win, parts, "none", 1.0)
    return out["adm_per_s"] * end, info["adm_per_s_by_stamp"] * end


@pytest.mark.parametrize("end, part", [(9.9, 0.475), (10.05, 0.5125),
                                       (10.2, 0.55), (12.0, 1.0),
                                       (13.0, 1.0)])
def test_adm_per_s_moves_by_the_part_of_the_closing_pass(end, part):
    replay, _win, parts = record(end)
    assert parts == [1.0, 1.0, pytest.approx(part)]
    kept = endtoend.by_pass(replay.passes, endtoend.kept_stamps(
        replay.reservations, replay.evictions, 0.0, end))
    # b3 was preempted away inside the window; b4 only after 11.5
    assert kept == [10, 9 if end < 11.5 else 8, 100]
    new, _old = adm(end)
    assert new == pytest.approx(10 + kept[1] + part * 100)


def test_the_count_by_the_stamp_steps_where_the_part_does_not():
    # 0.3 s of timing around the commit: by the stamp 19 -> 69 -> 119
    # admissions, by the part 66.5 -> 70.25 -> 74
    olds = [adm(e)[1] for e in (9.9, 10.05, 10.2)]
    news = [adm(e)[0] for e in (9.9, 10.05, 10.2)]
    assert olds == [pytest.approx(19), pytest.approx(70), pytest.approx(119)]
    assert news == [pytest.approx(66.5), pytest.approx(70.25),
                    pytest.approx(74.0)]
    assert max(news) - min(news) < 0.1 * (max(olds) - min(olds))
    # where every pass ends inside the window both agree
    new, old = adm(13.0)
    assert new == old == pytest.approx(118)


def test_pass_s_and_the_top_class_wait():
    a = types.SimpleNamespace
    replay, win, parts = record(10.0)
    replay.arrivals = [a(key="a0", klass="top", due_s=0.5),
                       a(key="c0", klass="top", due_s=-1.0),
                       a(key="zz", klass="top", due_s=2.0),
                       a(key="late", klass="top", due_s=11.0),
                       a(key="a1", klass="other", due_s=0.0),
                       a(key="b0", klass="other", due_s=5.0),
                       a(key="nobody", klass="other", due_s=9.0),
                       a(key="later", klass="idle", due_s=10.5)]
    out, window, info = endtoend.measure(replay, win, parts, "top", 7.0)
    assert out["pass_s"] == pytest.approx(10.0 / 2.5)
    assert out["setup_s"] == 7.0
    # a0 waited 0.5 s; c0 (created before the window) 10 s from its
    # start; zz never got a seat and had waited 8 s at the end
    assert info["top_due"] == 3 and info["top_still_waiting"] == 1
    assert out["tta_top_p95_s"] == pytest.approx(10.0)
    assert window["top_wait_p95_s"] == out["tta_top_p95_s"]
    # every class of the schedule by the same rule: ``top`` waited 0.5,
    # 10 (created before the window) and 8 s (still waiting); ``other``
    # 1.01 s, 0 s (b0 was seated at 4.0, before it was due) and 1 s
    # (still waiting); ``idle`` has nobody due in the window
    assert window["wait_mean_s.top"] == pytest.approx(18.5 / 3)
    assert window["wait_mean_s.top"] == info["top_wait_mean_s"]
    assert window["wait_p95_s.top"] == out["tta_top_p95_s"]
    assert window["wait_mean_s.other"] == pytest.approx(2.01 / 3)
    assert window["wait_p95_s.other"] == pytest.approx(1.01)
    assert set(window) == {"top_wait_p95_s"} | {
        f"wait_{stat}_s.{k}" for stat in ("mean", "p95")
        for k in ("top", "other")}
    assert info["top_wait_median_s"] == pytest.approx(8.0)
    empty = a(passes=[], reservations=[], evictions=[], arrivals=[],
              start_at=0.0)
    out, _w, _i = endtoend.measure(empty, win, [], "top", 1.0)
    assert out["adm_per_s"] == 0.0 and out["pass_s"] is None
    assert out["tta_top_p95_s"] is None
