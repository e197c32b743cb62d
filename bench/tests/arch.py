"""The configurations the tests read, each with its architecture module.

A test configuration lies in ``bench/tests/data``, and so does its
architecture module where it brings one of its own; the benchmark's
configurations and their modules are where the harness finds them.
"""

from typing import Any, Dict, List

from bench import common

DATA = common.BENCH / "tests" / "data"


def config(name: str) -> Dict[str, Any]:
    path = DATA / f"{name}.json"
    return common.load_json(path) if path.exists() else common.load_config(name)


def module(config: Dict[str, Any]):
    """A fresh copy of the module ``config`` names: a test's own from
    ``bench/tests/data`` where it brings one, else the harness's."""
    name = config["reference"]
    path = DATA / f"reference_{name}.py"
    if not path.exists():
        return common.reference_module(name)
    return common._load_module(path, f"bench_reference_{name}")


def every_config() -> List[str]:
    """Names of every configuration, the benchmark's and the tests'."""
    files = sorted((common.BENCH / "configs").glob("*.json")) + sorted(DATA.glob("*.json"))
    return [p.stem for p in files if "model" in common.load_json(p)]
