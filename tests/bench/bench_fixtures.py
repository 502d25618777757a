"""Tiny copies of the benchmark's cells for CPU tests: the same files and
drivers, at widths and lengths a test run can hold."""
import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]

TINY_MODEL = {"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 512}
TINY_TRAIN = {"seq_len": 64, "batch": 2}
TINY_TUNE = {"seq_len": 64, "batch": 1, "budget": 3}
#: the tune driver's re-timing, shortened for the tiny cell
TINY_RETIME = {"RETIME_ROUNDS = 3": "RETIME_ROUNDS = 2",
               "RETIME_BATCH_S = 0.25": "RETIME_BATCH_S = 0.02"}
TINY_ATTN = {"num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 16}
#: limits for the tiny train cell: above what the bf16 program reads at this
#: size on the CPU, below what the planted faults read
TINY_LIMITS = {"loss_gap": 5e-3, "grad_gap": 0.1, "change_gap": 0.1}


def _edit(path: pathlib.Path, **changes):
    path.write_text(json.dumps(dict(json.loads(path.read_text()), **changes)))


def _edit_source(path: pathlib.Path, replacements: dict):
    text = path.read_text()
    for old, new in replacements.items():
        assert old in text, (path, old)
        text = text.replace(old, new)
    path.write_text(text)


def tiny_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout-like directory whose cells are tiny: a copy of bench/ and
    BENCHMARK.json with the configuration and traffic files shrunk."""
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp / "BENCHMARK.json")
    b = tmp / "bench"
    _edit(b / "configs" / "qwen2-0.5b.json", **TINY_MODEL)
    _edit(b / "configs" / "h2o-danube-1.8b.json", **TINY_MODEL)
    _edit(b / "traffic" / "train-2x1024.json", **TINY_TRAIN)
    _edit(b / "traffic" / "train-2x2048.json", **TINY_TRAIN)
    _edit(b / "traffic" / "tune-attn.json", **TINY_TUNE)
    _edit_source(b / "drivers" / "tune.py", TINY_RETIME)
    for f in (b / "limits").glob("*train*.json"):
        f.write_text(json.dumps(TINY_LIMITS))
    return tmp


def tiny_tune_root(tmp: pathlib.Path) -> pathlib.Path:
    root = tiny_root(tmp)
    _edit(root / "bench" / "configs" / "qwen2-0.5b.json", **TINY_ATTN)
    return root
