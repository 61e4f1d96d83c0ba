"""The architecture seam: a configuration names the module under
``arch/`` that knows its model, and the harness calls that module's
functions (``run.ARCH_API``) and no model code by name.

A second architecture enters as files alone: a test-only Qwen2 without
q/k/v biases (``data/arch/qwen2_nobias.py``), given a bench directory of
its own (module, configuration, traffic, limits), goes through
``load_cell``, ``build_program``, the window, its own reference and
``judge`` with no file of ``bench/`` written.  Its prefill against its
reference is a case of ``test_bench_reference.py``."""

import glob
import json
import os
import re
import shutil

import pytest

from bench import run

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(ROOT, "bench")
DATA = os.path.join(os.path.dirname(__file__), "data")
ARCH_FILES = sorted(glob.glob(os.path.join(BENCH, "arch", "*.py"))
                    + glob.glob(os.path.join(DATA, "arch", "*.py")))
CONFIGS = sorted(glob.glob(os.path.join(BENCH, "configs", "*.json")))
HARNESS = ([os.path.join(BENCH, n) for n in ("run.py", "control.py")]
           + sorted(glob.glob(os.path.join(BENCH, "metrics", "*.py"))))
#: seconds of CPU window, as in test_bench_correct.py
WINDOW_S = 4.0
SEED = 2**33 + 77
CELL = "tiny-nobias.closed"
SPEC = {"workloads": [{"name": CELL, "config": "tiny-nobias",
                       "traffic": "closed", "chips": 1}],
        "end_to_end": [{"name": n, "unit": "x"} for n in
                       ("setup_s", "out_tok_s", "itl_p95_ms")],
        "per_layer": []}


def bench_dir(tmp_path):
    """A bench directory of the test's own, holding the test
    architecture's files and nothing of ``bench/``."""
    d = tmp_path / "bench"
    for src, dst in (("arch/qwen2_nobias.py", "arch/qwen2_nobias.py"),
                     ("tiny-nobias.json", "configs/tiny-nobias.json"),
                     ("tiny_closed.json", "traffic/closed.json"),
                     ("tiny-nobias_limits.json", f"limits/{CELL}.json")):
        (d / dst).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(DATA, src), d / dst)
    return d


def bench_files():
    """Every file under ``bench/`` but compiled bytecode, with its
    modification time and size."""
    out = {}
    for base, dirs, files in os.walk(BENCH):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            st = os.stat(os.path.join(base, f))
            out[os.path.join(base, f)] = (st.st_mtime_ns, st.st_size)
    return out


@pytest.mark.parametrize("path", ARCH_FILES, ids=os.path.basename)
def test_every_arch_module_provides_the_interface(path):
    mod = run._load_module("bench_arch_probe", path)
    for name in run.ARCH_API:
        assert callable(getattr(mod, name, None)), name


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_every_config_names_a_module_that_exists(path):
    with open(path) as f:
        c = json.load(f)
    arch = run.load_arch(c, path)
    assert os.path.dirname(arch.__file__) == os.path.join(BENCH, "arch")
    assert arch.paged_layers(c) >= 1


@pytest.mark.parametrize("path", HARNESS, ids=os.path.basename)
def test_the_harness_names_no_architecture(path):
    with open(path) as f:
        src = f.read()
    assert not re.search(r"bench\.arch|qwen", src, re.IGNORECASE)


@pytest.mark.parametrize("fault", ["no_key", "no_module", "no_function"])
def test_load_cell_refuses_a_config_without_its_module(tmp_path, fault):
    d = bench_dir(tmp_path)
    config = d / "configs" / "tiny-nobias.json"
    if fault == "no_function":
        module = d / "arch" / "qwen2_nobias.py"
        module.write_text(module.read_text() + "\ndel paged_layers\n")
        want = r"qwen2_nobias\.py lacks \['paged_layers'\]"
    else:
        c = json.loads(config.read_text())
        if fault == "no_key":
            del c["arch"]
            want = "names no architecture module"
        else:
            c["arch"] = "no_such_arch"
            want = "no_such_arch"
        config.write_text(json.dumps(c))
    with pytest.raises(ValueError, match=want) as e:
        run.load_cell(CELL, SPEC, str(d))
    assert str(config) in str(e.value)


def test_a_second_architecture_enters_as_files_only(tmp_path):
    before = bench_files()
    d = bench_dir(tmp_path)
    cell = run.load_cell(CELL, SPEC, str(d))
    assert cell.arch.__file__ == str(d / "arch" / "qwen2_nobias.py")
    cfg, _, params = run.build_program(cell, SEED)
    assert not cfg.qkv_bias
    assert set(params["trunk"]["attn"]["wq"]) == {"w"}

    res = run.run_cell(cell, SEED, WINDOW_S, False, control=True,
                       compile_cache=False)
    sound = res["program_checks"]
    assert sound["failed"]["value"] == 0
    assert sound["compiles_in_window"]["value"] == 0
    assert sound["sampled_tokens"]["value"] >= sound["sampled_tokens"]["limit"]
    assert sound["max_logit_gap"]["value"] <= sound["max_logit_gap"]["limit"]
    # the fp8 control, judged by the same checks in the served tokens' place
    assert not res["correct"]
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert bench_files() == before
