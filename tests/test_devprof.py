"""The on-demand device profile (utils/devprof.py
capture_device_profile): it writes a real trace directory and refuses a
concurrent capture."""

import os
import time

import pytest


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
