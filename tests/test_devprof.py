"""benchdiff (tools/benchdiff.py), bench.py's result stamp and the
on-demand device profile (utils/devprof.py capture_device_profile).

  - benchdiff verdict logic: direction/min-effect policies, IQR noise
    bands over a baseline series, the config-fingerprint refusal, exit
    codes, and the markdown table — plus bench.stamp_result fingerprint
    stability (same config → same stamp; any knob change → different).
  - capture_device_profile writes a real trace directory and refuses a
    concurrent capture.
"""

import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from tools.benchdiff import compare, flatten, policy_for  # noqa: E402
from tools.benchdiff import main as benchdiff_main  # noqa: E402


class TestBenchStamp:
    def _mk(self, **over):
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        import bench

        result = {"value": 100.0, "unit": "tok/s"}
        cfg = {"slots": 2, "clients": 8, "quant": "int8", **over}
        return bench.stamp_result(dict(result), cfg, "smoke")

    def test_stamp_is_stable_and_config_sensitive(self):
        a, b = self._mk(), self._mk()
        assert a["schema"] == 1
        assert a["config_fingerprint"] == b["config_fingerprint"]
        assert a["config"]["mode"] == "smoke"
        c = self._mk(slots=4)
        assert c["config_fingerprint"] != a["config_fingerprint"]


class TestBenchdiff:
    def _capture(self, value=100.0, ttft=1.0, fp="aaaa", **extra):
        return {"schema": 1, "git_sha": "deadbeef", "written_at": 0,
                "config": {"mode": "smoke", "slots": 2},
                "config_fingerprint": fp,
                "metric": "x", "unit": "tok/s",
                "value": value, "ttft_p50_s": ttft,
                "tokens_streamed": 4096, **extra}

    def test_flatten_skips_meta_and_nests(self):
        flat = flatten(self._capture(engine={"decode_step_ms": 2.0}))
        assert flat["value"] == 100.0
        assert flat["engine.decode_step_ms"] == 2.0
        assert "config.slots" not in flat
        assert "schema" not in flat

    def test_policies_match_expected_directions(self):
        assert policy_for("value") == ("higher", 0.03)
        assert policy_for("ttft_p50_s")[0] == "lower"
        assert policy_for("engine.decode_step_ms")[0] == "lower"
        assert policy_for("shared_prefix.ttft_p50_cached_s")[0] == "lower"
        assert policy_for("tokens_streamed") is None  # workload-sized

    def test_pairwise_verdicts(self):
        base = self._capture()
        rows = compare([base], self._capture(value=80.0, ttft=1.5))
        by = {r["metric"]: r for r in rows}
        assert by["value"]["verdict"] == "REGRESSED"       # -20% tok/s
        assert by["ttft_p50_s"]["verdict"] == "REGRESSED"  # +50% latency
        assert by["tokens_streamed"]["verdict"] == "info"
        rows = compare([base], self._capture(value=110.0, ttft=0.5))
        by = {r["metric"]: r for r in rows}
        assert by["value"]["verdict"] == "improved"
        assert by["ttft_p50_s"]["verdict"] == "improved"
        # Inside the min-effect band: ok, regardless of sign.
        rows = compare([base], self._capture(value=99.0, ttft=1.02))
        by = {r["metric"]: r for r in rows}
        assert by["value"]["verdict"] == "ok"
        assert by["ttft_p50_s"]["verdict"] == "ok"

    def test_series_iqr_widens_the_band(self):
        # A noisy metric: baseline runs spread 80..120, so a candidate
        # at 85 is within the measured noise even though it is >3%
        # below the last baseline — the IQR band must absorb it.
        series = [self._capture(value=v)
                  for v in (80.0, 100.0, 120.0, 95.0, 105.0)]
        rows = compare(series, self._capture(value=85.0))
        by = {r["metric"]: r for r in rows}
        assert by["value"]["verdict"] == "ok"
        # A genuinely-off candidate still regresses through the band.
        rows = compare(series, self._capture(value=40.0))
        by = {r["metric"]: r for r in rows}
        assert by["value"]["verdict"] == "REGRESSED"

    def _write(self, tmp_path, name, obj):
        p = tmp_path / name
        p.write_text(json.dumps(obj))
        return str(p)

    def test_cli_exit_codes_and_markdown(self, tmp_path, capsys):
        base = self._write(tmp_path, "base.json", self._capture())
        same = self._write(tmp_path, "same.json", self._capture())
        worse = self._write(tmp_path, "worse.json",
                            self._capture(value=50.0))
        out_md = tmp_path / "delta.md"
        assert benchdiff_main([base, same, "--out", str(out_md)]) == 0
        text = capsys.readouterr().out
        assert "| metric |" in text and "REGRESSED" not in text
        assert out_md.read_text().startswith("# benchdiff")
        assert benchdiff_main([base, worse]) == 1
        assert "REGRESSED" in capsys.readouterr().out

    def test_cli_refuses_fingerprint_mismatch(self, tmp_path, capsys):
        base = self._write(tmp_path, "base.json", self._capture())
        other = self._write(
            tmp_path, "other.json",
            self._capture(fp="bbbb") | {"config": {"mode": "smoke",
                                                   "slots": 99}})
        assert benchdiff_main([base, other]) == 2
        err = capsys.readouterr().err
        assert "REFUSING" in err and "slots" in err
        # --force compares anyway and names the differing knobs.
        rc = benchdiff_main([base, other, "--force"])
        assert rc in (0, 1)
        assert "forced" in capsys.readouterr().err

    def test_cli_refuses_unstamped_without_force(self, tmp_path, capsys):
        cap = self._capture()
        legacy = {k: v for k, v in cap.items()
                  if k not in ("schema", "config", "config_fingerprint")}
        base = self._write(tmp_path, "legacy.json", legacy)
        cand = self._write(tmp_path, "cand.json", self._capture())
        assert benchdiff_main([base, cand]) == 2
        assert "unstamped" in capsys.readouterr().err
        assert benchdiff_main([base, cand, "--force"]) in (0, 1)

    def test_cli_json_mode(self, tmp_path, capsys):
        base = self._write(tmp_path, "base.json", self._capture())
        worse = self._write(tmp_path, "worse.json",
                            self._capture(value=50.0))
        assert benchdiff_main([base, worse, "--json"]) == 1
        payload = json.loads(capsys.readouterr().out)
        assert payload["regressed"] is True
        assert any(r["verdict"] == "REGRESSED" for r in payload["rows"])


class TestCaptureDeviceProfile:
    def test_capture_writes_artifacts_and_single_flights(self, tmp_path):
        import threading

        from symmetry_tpu.utils.devprof import capture_device_profile

        path = capture_device_profile(str(tmp_path), duration_s=0.05)
        assert os.path.isdir(path)
        # Concurrent capture refused while one holds the window.
        hold = threading.Thread(target=capture_device_profile,
                                args=(str(tmp_path),),
                                kwargs={"duration_s": 0.5})
        hold.start()
        time.sleep(0.15)
        with pytest.raises(RuntimeError, match="already running"):
            capture_device_profile(str(tmp_path), duration_s=0.05)
        hold.join()
