"""Run with ``python -m pytest bench/tests`` from the checkout's root."""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import json  # noqa: E402

import pytest  # noqa: E402

# cells the tests drive, added to a copy of BENCHMARK.json where it does
# not list them: the STFT configuration and mix, and the BL ones on a
# four-device mesh
EXTRA_CONFIGS = [{"name": "librosa_stft_2048",
                  "source": "https://librosa.org/doc/main/generated/"
                            "librosa.stft.html",
                  "file": "bench/configs/librosa_stft_2048.json",
                  "reduced": [], "why": "test"}]
EXTRA_CELLS = [
    {"name": "stft_librosa.stream", "config": "librosa_stft_2048",
     "traffic": "stream", "chips": 1, "why": "test"},
    {"name": "mesh", "config": "bl_hires_2p20", "traffic": "batch",
     "chips": 4, "why": "test"},
]


@pytest.fixture
def spec_root(tmp_path):
    """A checkout root whose BENCHMARK.json lists every cell the tests
    drive; ``bench/`` is the real one."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    have = {c["name"] for c in spec["configs"]}
    spec["configs"] += [c for c in EXTRA_CONFIGS if c["name"] not in have]
    have = {w["name"] for w in spec["workloads"]}
    spec["workloads"] += [w for w in EXTRA_CELLS if w["name"] not in have]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "bench").symlink_to(ROOT / "bench")
    return tmp_path
