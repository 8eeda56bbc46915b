import json
import sys
from pathlib import Path

import pytest

_HERE = Path(__file__).resolve().parent
for p in (str(_HERE), str(_HERE.parents[1]), str(_HERE.parents[1] / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="session")
def tiny_config():
    return json.loads((_HERE / "fixtures" / "tiny.json").read_text())
