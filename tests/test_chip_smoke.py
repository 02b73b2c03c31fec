"""chip_smoke.py on the CPU at tiny sizes: its phases, its independent
checks, and its exit contract (non-zero and no result off the chip)."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.core import modularity  # noqa: E402
from repro.graph import from_undirected, sbm_graph  # noqa: E402


def _two_triangles():
    u = np.array([0, 1, 2, 3, 4, 5])
    v = np.array([1, 2, 0, 4, 5, 3])
    return from_undirected(6, u, v)


def test_smoke_exits_nonzero_off_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_smoke_alone_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_host_disconnected_catches_planted_labelling():
    g = _two_triangles()
    one_label = np.zeros(7, np.int32)             # two triangles, one label
    assert chip_smoke.host_disconnected(g, one_label) == 1
    split = np.array([0, 0, 0, 1, 1, 1, 2], np.int32)
    assert chip_smoke.host_disconnected(g, split) == 0
    with pytest.raises(AssertionError, match="disconnected"):
        chip_smoke.check_partition(g, one_label,
                                   chip_smoke.host_modularity(g, one_label),
                                   "planted")


def test_host_modularity_matches_core():
    g = sbm_graph(n_nodes=60, n_blocks=3, p_in=0.4, p_out=0.05, seed=2)[0]
    labels = np.arange(g.nv, dtype=np.int32) % 4
    q = float(modularity(g.src, g.dst, g.w, labels, seg_impl="xla"))
    assert chip_smoke.host_modularity(g, labels) == pytest.approx(q,
                                                                  abs=1e-5)
    assert chip_smoke.host_modularity(_two_triangles(), np.array(
        [0, 0, 0, 1, 1, 1, 2])) == pytest.approx(0.5)


def test_service_requests_cover_every_bucket():
    from repro.service.buckets import DEFAULT_BUCKETS
    reqs = chip_smoke.service_requests(0, 2)
    assert {b for *_, b in reqs} == set(DEFAULT_BUCKETS)
    assert {t for _, t, _, _ in reqs} == set(chip_smoke.TENANTS)


def test_phase_service_tiny():
    rep = chip_smoke.phase_service(0, per_bucket=2, n_sequential=1,
                                   n_update=1, buckets=(0, 4))
    assert rep["served"] == 4 + 2 * 2
    assert set(rep["parity"]) == {"64x512", "1024x16384"}
    assert all(p["dense_eq_sort"] and p["pallas_eq_xla"]
               for p in rep["parity"].values())
    assert all(c["misses"] >= 1 and c["seconds"] > 0
               for c in rep["compiles"].values())


def test_phase_large_tiny():
    rep = chip_smoke.phase_large(9, 0, ref_scale=8)
    assert rep["ref_same"] and rep["m"] > 0 and rep["seconds"] > 0


def test_phase_sharded_tiny():
    rep = chip_smoke.phase_sharded(8, 0, chips=1)
    assert rep["halo_bytes"] > 0


@pytest.mark.parametrize("fail", [False, True])
def test_main_result_line(monkeypatch, capsys, fail):
    from repro.launch import compile_cache

    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda chips: dev)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "-")

    def service(seed):
        if fail:
            raise AssertionError("planted failure")
        return {"seg_impl": "pallas", "interpret": False}

    monkeypatch.setattr(chip_smoke, "phase_service", service)
    monkeypatch.setattr(chip_smoke, "phase_large",
                        lambda seed: {"seg_impl": "pallas"})
    rc = chip_smoke.main([])
    lines = capsys.readouterr().out.strip().splitlines()
    if fail:
        assert rc == 1 and '"ok"' not in lines[-1]
    else:
        assert rc == 0
        assert json.loads(lines[-1]) == {"ok": True, "device": dev}
