import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def small_reference_blocks(monkeypatch):
    """The tests' sequences are short: pad the reference's inputs to small
    blocks, not to the chat mix's 2048 positions."""
    from bench.arch import bitnet

    monkeypatch.setattr(bitnet, "SEQ_BLOCK", 512)
    monkeypatch.setattr(bitnet, "ROW_BLOCK", 256)
