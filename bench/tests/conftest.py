"""Tests of the benchmark itself.  Run them from this directory's parent:

    cd bench && python -m pytest tests -q

They put `bench/` (the harness) and `src/` (the program) on the path."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


# Tiny stand-ins for the cells' sizes, for runs on the CPU in Pallas
# interpret mode: the same code paths at a few slots and short contexts.
TINY_CONFIG = {"num_key_value_heads": 2, "num_attention_heads": 4,
               "assumed": {"head_dim": 32, "page": 16,
                           "kv_dtype": "bfloat16"}}
TINY_MIX = {
    "endless": {"slots": 4, "sessions": 4, "capacity_tokens": 2048,
                "first_prompt": {"choices": [576, 608, 640, 672]}},
    "chat": {"slots": 4, "sessions": 10, "capacity_tokens": 512,
             "replacements": 8, "turns_per_session": 8,
             "first_turn_stagger_s": 0.5,
             "first_prompt": {"lognormal": {"median": 128, "sigma": 0.5},
                              "min": 64, "max": 256, "round_up": 64},
             "turn": {"user": {"lognormal": {"median": 64, "sigma": 0.5},
                               "min": 32, "max": 128, "round_up": 32},
                      "answer": {"lognormal": {"median": 8, "sigma": 0.5},
                                 "min": 4, "max": 16, "round_up": 1},
                      "think_s": {"exponential_mean": 0.5, "max": 3.0}}},
}


# The chat mix (`traffic/chat_sessions_spill.json`) has no cell in
# BENCHMARK.json yet (see PERF.md, Open questions); its tests read it
# from a copy that adds the cell.
CHAT_CELL = {"name": "olmoe.chat_sessions.spill", "config": "olmoe_1b_7b",
             "traffic": "chat_sessions_spill", "chips": 1,
             "why": "closed-loop chats over a spilling pool"}
CHAT_CONFIG = {"name": "olmoe_1b_7b", "source": "-",
               "file": "bench/configs/olmoe_1b_7b.json", "reduced": [],
               "why": "-"}


def chat_benchmark() -> Path:
    import json
    import tempfile

    bm = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bm["configs"].append(CHAT_CONFIG)
    bm["workloads"].append(CHAT_CELL)
    for name, unit, moves in (("ttft_p90_ms", "ms", None),):
        bm["end_to_end"].append({"name": name, "unit": unit,
                                 "better": "lower", "bound": 0.1,
                                 "source": "host_clock",
                                 "workloads": [CHAT_CELL["name"]]})
    for m in ("host_ms_per_step.churn", "attend_roofline.churn",
              "step_mfu.churn", "spill_crossings_per_turn",
              "device_idle_share.churn"):
        bm["per_layer"].append({"name": m, "unit": "-", "better": "lower",
                                "source": "device_trace", "layer": "-",
                                "moves": "itl_p95_ms",
                                "workloads": [CHAT_CELL["name"]]})
    path = Path(tempfile.mkdtemp(prefix="bench_chat_")) / "BENCHMARK.json"
    path.write_text(json.dumps(bm))
    return path


def tiny(cell_name: str) -> dict:
    kind = "chat" if cell_name.startswith("olmoe") else "endless"
    return {"config": TINY_CONFIG, "mix": TINY_MIX[kind]}


def run_tiny(cell_name: str, *, seed: int = 2**31 + 12345,
             seconds: float = 2.0, trace_on: bool = False,
             control: bool = False) -> dict:
    """One run of a cell at tiny size on the CPU (no chip check)."""
    from harness import cell

    chat = cell_name == CHAT_CELL["name"]
    return cell.run(cell_name, seed=seed, seconds=seconds,
                    trace_on=trace_on, require_tpu=False,
                    peaks_kind="TPU v5 lite", control=control,
                    overrides=tiny(cell_name),
                    benchmark=chat_benchmark() if chat else None)
