"""The open-loop serving cell at a tiny size on the CPU: a sound run is
correct, and the control and an altered answer come out not correct."""

CELL = "serve.sift128_k512.zipf4"
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def test_sound_run_line(run_tiny):
    line = run_tiny(CELL)
    assert list(line) == KEYS
    assert line["correct"] is True
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"query_p50_ms", "setup_s"}
    assert line["metrics"]["query_p50_ms"]["value"] > 0.0


def test_control_is_not_correct(readings_tiny):
    got, limits = readings_tiny(CELL)
    assert all(got["sound"][k] <= v for k, v in limits.items()), got
    assert any(got["control"][k] > v for k, v in limits.items()), got


def test_answer_altered(run_tiny, monkeypatch):
    import repro.serve.frontend as fe

    make = fe._batch_assign_fn

    def altered(impl):
        run = make(impl)

        def assign(q, c):
            idx, dist = run(q, c)
            return idx, dist * 1.01

        return assign

    monkeypatch.setattr(fe, "_batch_assign_fn", altered)
    assert run_tiny(CELL)["correct"] is False
