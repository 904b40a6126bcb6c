"""OMNeT++ .vec/.sca result recording (native vecwriter + fallback)."""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu import recorder
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.myoverlay import MyOverlayLogic, MyOverlayParams


def _sim(n=4, seed=9):
    # same shape as tests/test_gateway.py's ring sim (compile reuse)
    logic = MyOverlayLogic(params=MyOverlayParams(),
                           app=None)
    cp = churn_mod.ChurnParams(model="none", target_num=n,
                               init_interval=0.2)
    ep = sim_mod.EngineParams(window=0.020, inbox_slots=2)
    return sim_mod.Simulation(logic, cp, engine_params=ep)


def test_native_writer_builds():
    # the C library must build on this image (gcc is baked in); the
    # pure-Python fallback keeps the feature alive elsewhere
    assert recorder._load() is not None


def _parse_vec(path):
    header, vectors, rows = [], {}, []
    for line in open(path):
        parts = line.rstrip("\n").split("\t")
        if line.startswith("vector "):
            _, vid, module, name, kind = line.split()
            vectors[int(vid)] = (module, name, kind)
        elif len(parts) == 3:
            rows.append((int(parts[0]), float(parts[1]),
                         float(parts[2])))
        else:
            header.append(line.strip())
    return header, vectors, rows


def test_vec_and_sca_roundtrip(tmp_path):
    s = _sim()
    state = s.init(seed=3)
    vec = tmp_path / "out.vec"
    sca = tmp_path / "out.sca"
    rec = recorder.VectorRecorder(s, vec, run_id="ring-0")
    state = rec.run(state, t_sim=60.0, sample_every=10.0)
    rec.close()
    recorder.write_scalars(s, state, sca, run_id="ring-0")

    header, vectors, rows = _parse_vec(vec)
    assert "version 2" in header[0]
    assert any(h.startswith("run ring-0") for h in header)
    names = {name for (_, name, _) in vectors.values()}
    assert "aliveNodes" in names
    assert all(kind == "TV" for (_, _, kind) in vectors.values())
    # ~6 samples of each declared vector, times strictly increasing
    alive_id = next(vid for vid, (_, n2, _) in vectors.items()
                    if n2 == "aliveNodes")
    alive_rows = [(t, v) for vid, t, v in rows if vid == alive_id]
    assert len(alive_rows) >= 3  # run_until overshoots chunk-wise
    ts = [t for t, _ in alive_rows]
    assert ts == sorted(ts)
    assert alive_rows[-1][1] == 4.0  # all four nodes alive

    sca_lines = open(sca).read().splitlines()
    assert sca_lines[0] == "version 2"
    assert any(line.startswith("scalar ") and " aliveNodes " in line
               for line in sca_lines)


def test_fallback_warns_once_and_uses_py_writer(tmp_path, monkeypatch,
                                                capsys):
    """A failed native build must NOT be silent: one stderr line, once,
    then every writer request gets the pure-Python fallback."""
    monkeypatch.setattr(recorder, "_build", lambda: False)
    monkeypatch.setattr(recorder, "_lib", None)
    monkeypatch.setattr(recorder, "_failed", False)
    w1 = recorder._writer(tmp_path / "w1.vec", "r")
    w2 = recorder._writer(tmp_path / "w2.vec", "r")
    w1.close()
    w2.close()
    assert isinstance(w1, recorder._PyWriter)
    assert isinstance(w2, recorder._PyWriter)
    err = capsys.readouterr().err
    assert err.count("native vecwriter build failed") == 1
    # monkeypatch restores _lib/_failed afterwards — later tests still
    # see the real native writer


def test_python_fallback_identical_format(tmp_path):
    a = tmp_path / "a.vec"
    b = tmp_path / "b.vec"
    lib = recorder._load()
    if lib is None:
        pytest.skip("no native writer to compare against")
    wn = recorder._CWriter(lib, a, "x")
    wp = recorder._PyWriter(b, "x")
    for w in (wn, wp):
        vid = w.declare("m", "n")
        w.rows(vid, np.asarray([1.0, 2.5]), np.asarray([3.0, 4.125]))
        w.scalar("m", "s", 7.25)
        w.close()
    assert a.read_text() == b.read_text()
