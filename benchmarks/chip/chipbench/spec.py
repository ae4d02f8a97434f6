"""Find a cell's configuration, traffic mix and per-layer metrics by name.

``BENCHMARK.json`` at the checkout's root names them; each lives in a file
of its own under the benchmark's directory, so a later change adds a
configuration, a mix or a metric as new files and new entries and edits
no file that is there.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclasses.dataclass
class Config:
    name: str
    model: Any                 # repro.configs.base.ModelConfig
    meta: Dict[str, Any]       # everything else in the file


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Config
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: Path = BENCH_DIR


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def model_config(d: Dict[str, Any]):
    """A ``ModelConfig`` from its JSON form (nested groups as dicts)."""
    from repro.configs import base as B
    sub = {"attn": B.AttnConfig, "attn_local": B.AttnConfig,
           "moe": B.MoEConfig, "ssd": B.SSDConfig, "rglru": B.RGLRUConfig}
    kw = dict(d)
    for k, cls in sub.items():
        if kw.get(k) is not None:
            kw[k] = cls(**kw[k])
    kw["pattern"] = tuple(tuple(p) for p in kw["pattern"])
    return B.ModelConfig(**kw)


def load_config(entry: Dict[str, Any], root: Path = ROOT) -> Config:
    path = Path(root) / entry["file"]
    data = json.loads(path.read_text())
    if data.get("name") != entry["name"]:
        raise SpecError(f"{path} holds {data.get('name')!r}, "
                        f"not {entry['name']!r}")
    meta = {k: v for k, v in data.items() if k != "model"}
    return Config(entry["name"], model_config(data["model"]), meta)


def load_traffic(name: str, bench_dir: Path = BENCH_DIR) -> Dict[str, Any]:
    path = Path(bench_dir) / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def metric_reader(name: str, bench_dir: Path = BENCH_DIR
                  ) -> Callable[[Any], Optional[float]]:
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = Path(bench_dir) / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader for metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: Dict[str, Any], cell: str,
             e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


def find_cell(name: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; BENCHMARK.json has "
                        f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_config(configs[w["config"]], root)
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, e2e_names)]
    return Cell(name, int(w["chips"]), config, w["traffic"],
                load_traffic(w["traffic"], bench_dir), e2e, per_layer,
                Path(bench_dir))
