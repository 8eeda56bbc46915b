"""Helpers shared by the benchmark's tests."""
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / "fixtures"


def tiny_cell(mix_name: str, end_to_end=None, per_layer=None):
    """A cell of the tiny fixture configuration under a fixture mix."""
    from bench import harness as H
    from bench import models, traffic

    names = end_to_end or ["setup_s", "ttft_p50_ms", "ttft_p95_ms",
                           "tpot_p95_ms", "tokens_per_s"]
    config = json.loads((FIXTURES / "tiny.json").read_text())
    return H.Cell(
        name=f"tiny.{mix_name}", config=config,
        mix=traffic.load_mix(FIXTURES / f"{mix_name}.json"), chips=1,
        end_to_end=[{"name": n, "unit": "x"} for n in names],
        per_layer=per_layer or [], family=models.family(config["model"]))
