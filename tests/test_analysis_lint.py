"""Self-test for repro-lint: each corpus file fires exactly its rule,
the shipped tree stays clean, and the escape hatches actually silence."""

import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import lint

REPO = Path(__file__).resolve().parent.parent
CORPUS = REPO / "tests" / "lint_corpus"

# one bad file per rule -> the only slug it may emit
CORPUS_SLUGS = {
    "bad_private_jax.py": "private-jax",
    "bad_host_leak.py": "host-leak-in-jit",
    "bad_pallas_lane.py": "pallas-lane",
    "bad_pallas_smem_order.py": "pallas-smem-order",
    "bad_pallas_interpret.py": "pallas-interpret-literal",
    "core/bad_unplaced.py": "core-unplaced",
    "bad_raw_env.py": "raw-env",
}


def test_corpus_covers_every_rule():
    assert set(CORPUS_SLUGS.values()) == set(lint.RULES)


@pytest.mark.parametrize("relpath,slug", sorted(CORPUS_SLUGS.items()))
def test_corpus_file_fires_exactly_its_rule(relpath, slug):
    violations = lint.lint_paths([str(CORPUS / relpath)])
    assert violations, f"{relpath} should violate {slug}"
    assert {v.slug for v in violations} == {slug}, \
        [v.render() for v in violations]
    code = lint.RULES[slug][0]
    for v in violations:
        assert v.code == code
        assert relpath.replace("/", "") in v.path.replace("/", "") \
            .replace("\\", "")


def test_shipped_tree_is_clean():
    paths = [str(REPO / d) for d in ("src", "tests", "benchmarks")]
    violations = lint.lint_paths(paths)
    assert violations == [], [v.render() for v in violations]


def test_walker_skips_the_corpus():
    files = [str(p) for p in lint.iter_py_files([str(REPO / "tests")])]
    assert files, "walker found no test files?"
    assert not any("lint_corpus" in f for f in files)


def test_cli_exit_codes():
    env_path = str(REPO / "src")
    bad = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint",
         str(CORPUS / "bad_raw_env.py")],
        capture_output=True, text=True, env={"PYTHONPATH": env_path,
                                             "PATH": "/usr/bin:/bin"})
    assert bad.returncode == 1
    assert "RPR008" in bad.stdout
    good = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint",
         str(REPO / "src" / "repro" / "analysis")],
        capture_output=True, text=True, env={"PYTHONPATH": env_path,
                                             "PATH": "/usr/bin:/bin"})
    assert good.returncode == 0, good.stdout + good.stderr
    assert "clean" in good.stdout


def test_allow_annotation_silences():
    noisy = "import os\nv = os.environ.get('X')\n"
    assert lint.lint_source(noisy)
    quiet = ("import os\n"
             "# why this is fine  # repro-lint: allow[raw-env]\n"
             "v = os.environ.get('X')\n")
    assert lint.lint_source(quiet) == []
    trailing = ("import os\n"
                "v = os.environ.get('X')  # repro-lint: allow[raw-env]\n")
    assert lint.lint_source(trailing) == []


def test_unplaced_annotation_silences():
    src = ("def f(weights, times):\n"
           "    return weights + times\n")
    assert lint.lint_source(src, path="src/repro/core/foo.py")
    annotated = ("# caller pins  # repro-lint: unplaced\n" + src)
    assert lint.lint_source(annotated, path="src/repro/core/foo.py") == []


def test_unplaced_only_fires_under_core():
    src = ("def f(weights, times):\n"
           "    return weights + times\n")
    assert lint.lint_source(src, path="src/repro/serve/foo.py") == []


def test_maybe_wsc_credits_transitively():
    src = ("from repro.sharding import specs as sharding_specs\n"
           "def pinner(x):\n"
           "    return sharding_specs.maybe_wsc(x, 'column')\n"
           "def f(weights, times):\n"
           "    return pinner(weights + times)\n")
    assert lint.lint_source(src, path="src/repro/core/foo.py") == []


def test_taint_launders_shape_but_not_values():
    clean = ("import jax\n"
             "@jax.jit\n"
             "def f(x):\n"
             "    if x.shape[0] == 1:\n"
             "        return x\n"
             "    return x + x.ndim\n")
    assert lint.lint_source(clean) == []
    leaky = ("import jax\n"
             "@jax.jit\n"
             "def f(x):\n"
             "    if x.sum() > 0:\n"
             "        return x\n"
             "    return float(x)\n")
    slugs = [v.slug for v in lint.lint_source(leaky)]
    assert slugs == ["host-leak-in-jit", "host-leak-in-jit"]


def test_taint_exempts_is_none_checks():
    src = ("import jax\n"
           "@jax.jit\n"
           "def f(x, aux=None):\n"
           "    if aux is None:\n"
           "        return x\n"
           "    return x + aux\n")
    assert lint.lint_source(src) == []


def test_private_jax_exempt_in_compat():
    src = "from jax._src.core import Tracer\n"
    assert lint.lint_source(src, path="src/repro/sharding/compat.py") == []
    assert lint.lint_source(src, path="src/repro/core/neuron.py")


def test_list_rules_mentions_every_code():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", "--list-rules"],
        capture_output=True, text=True,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 0
    for code, _ in lint.RULES.values():
        assert code in proc.stdout
