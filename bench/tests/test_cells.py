"""Each cell at its rehearsal sizes on the CPU: a sound run is correct;
the timed path broken underneath (an answer altered where the middleware
produces it, or requests that never come back) makes ``correct`` false;
and the control, the reference one precision lower, fails a limit."""
import functools
import json

import numpy as np
import pytest

import harness

with open(harness.MANIFEST) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]
SEED = 3000000017
SECONDS = 1.5


@pytest.fixture(scope="module", params=CELLS)
def sound(request):
    cell = harness.resolve(request.param)
    return cell, harness.run(cell, SEED, SECONDS, False, rehearse=True,
                             keep_host=True)


def test_sound_run_is_correct(sound):
    cell, res = sound
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    public = {k: v for k, v in res.items() if not k.startswith("_")}
    assert list(public)[-1] == "checks"
    json.dumps(public)


def _alter(rows, result):
    """One element of the answer changed: an index answer's first row
    swapped for one half of ``rows`` away, a value answer's first value
    moved by 1."""
    from repro.core.tables import DenseTensor
    import jax.numpy as jnp
    data = np.array(result.data)
    if np.issubdtype(data.dtype, np.integer):
        data.flat[0] = (data.flat[0] + rows // 2) % rows
    else:
        data.flat[0] += 1.0
    return DenseTensor(jnp.asarray(data))


class _Drop:
    """Every third request after the set-up's warm serves never comes
    back."""

    def __init__(self):
        self.calls = 0

    def __call__(self, result):
        self.calls += 1
        if self.calls > 8 and self.calls % 3 == 0:
            raise RuntimeError("request lost")
        return result


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("lost", [False, True], ids=["altered", "lost"])
def test_broken_timed_path_is_not_correct(monkeypatch, cell_name, lost):
    cell = harness.resolve(cell_name)
    rows = cell.config["rehearsal"]["patients"]
    fault = _Drop() if lost else functools.partial(_alter, rows)
    from repro.core.middleware import BigDAWG
    execute = BigDAWG.execute

    def broken(self, query, mode="auto", **kw):
        rep = execute(self, query, mode, **kw)
        if rep.mode == "production":
            rep.result = fault(rep.result)
        return rep

    monkeypatch.setattr(BigDAWG, "execute", broken)
    res = harness.run(cell, SEED, SECONDS, False, rehearse=True)
    assert not res["correct"]
    over = [k for k, c in res["checks"].items() if c["value"] > c["limit"]]
    assert over == ["unanswered"] if lost else over


def test_control_fails_a_limit(sound):
    cell, res = sound
    cfg, host = res["_cfg"], res["_host"]
    checks = cell.config_mod.reference(cfg, host,
                                       cell.config_mod.control(cfg, host))
    assert any(v > lim for v, lim in checks.values()), checks
