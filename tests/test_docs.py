"""Docs-suite checks: ``docs/flags.md`` must agree with the argparse
definitions (both directions, per CLI), the docs pages and README
landing page must exist and cross-link, and the public serving surface
must carry docstrings (the same D1 rules ``ruff.toml`` enforces,
re-checked here via ast so the suite doesn't depend on ruff being
installed)."""
import ast
import importlib
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = os.path.join(ROOT, "docs")

# every CLI module exposing build_parser() <-> its docs/flags.md section
CLIS = ["serve", "ltfb", "distributed", "train", "lineage"]


def _parser_flags(mod: str):
    m = importlib.import_module(f"repro.launch.{mod}")
    ap = m.build_parser()
    flags = set()
    for a in ap._actions:
        for opt in a.option_strings:
            if opt.startswith("--") and opt != "--help":
                flags.add(opt)
    return flags


def _doc_sections():
    """Split docs/flags.md into {module: section text}."""
    text = open(os.path.join(DOCS, "flags.md")).read()
    sections = {}
    current = None
    for line in text.splitlines():
        m = re.match(r"^## repro\.launch\.(\w+)\s*$", line)
        if m:
            current = m.group(1)
            sections[current] = []
        elif current is not None:
            sections[current].append(line)
    return {k: "\n".join(v) for k, v in sections.items()}


def test_flags_doc_has_a_section_per_cli():
    sections = _doc_sections()
    assert set(CLIS) == set(sections), (
        "docs/flags.md sections out of sync with the build_parser CLIs")


@pytest.mark.parametrize("mod", CLIS)
def test_flags_doc_matches_argparse(mod):
    """Both directions: documented ⊆ parser and parser ⊆ documented."""
    sections = _doc_sections()
    documented = set(re.findall(r"`(--[a-z][a-z0-9-]*)`", sections[mod]))
    actual = _parser_flags(mod)
    assert documented - actual == set(), (
        f"docs/flags.md documents flags {sorted(documented - actual)} "
        f"that repro.launch.{mod} does not define")
    assert actual - documented == set(), (
        f"repro.launch.{mod} defines flags {sorted(actual - documented)} "
        f"missing from docs/flags.md — document them")


def test_docs_suite_exists_and_crosslinks():
    pages = ["architecture.md", "serving.md", "deployment.md",
             "observability.md", "flags.md"]
    for p in pages:
        path = os.path.join(DOCS, p)
        assert os.path.exists(path), f"docs/{p} missing"
        assert len(open(path).read()) > 500, f"docs/{p} is a stub"
    readme = open(os.path.join(ROOT, "README.md")).read()
    for p in pages[:3]:
        assert f"docs/{p}" in readme, f"README does not link docs/{p}"
    # landing page, not a manual: the deep operational detail moved out
    assert len(readme.splitlines()) < 120, (
        "README grew past a landing page — move detail into docs/")


def test_deploy_artifacts_exist():
    assert os.path.exists(os.path.join(ROOT, "deploy", "Dockerfile"))
    launch = os.path.join(ROOT, "deploy", "launch.sh")
    assert os.path.exists(launch)
    assert os.access(launch, os.X_OK), "deploy/launch.sh not executable"
    text = open(launch).read()
    assert "tcmalloc" in text and "xla_force_host_platform_device_count" \
        in text


# -- docstring coverage (mirrors the ruff D1 scope) -------------------------

SERVE_DIR = os.path.join(ROOT, "src", "repro", "serve")


def _missing_docstrings(path: str):
    """Public defs/classes without docstrings, D1-style: underscore
    names are private; nested defs inside functions don't count;
    __init__/dunders are exempt (D105/D107 are ignored in ruff.toml)."""
    tree = ast.parse(open(path).read())
    missing = []

    def walk(node, prefix, in_class):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                name = child.name
                public = not name.startswith("_")
                if public and ast.get_docstring(child) is None:
                    missing.append(f"{prefix}{name}")
                if isinstance(child, ast.ClassDef) and public:
                    walk(child, f"{prefix}{name}.", True)
            elif not in_class and isinstance(child, ast.Module):
                walk(child, prefix, False)
    if ast.get_docstring(tree) is None:
        missing.append("<module>")
    walk(tree, "", False)
    return missing


def test_public_serve_surface_has_docstrings():
    problems = {}
    for fname in sorted(os.listdir(SERVE_DIR)):
        if not fname.endswith(".py"):
            continue
        missing = _missing_docstrings(os.path.join(SERVE_DIR, fname))
        if missing:
            problems[fname] = missing
    assert problems == {}, (
        f"public serve symbols missing docstrings: {problems}")


def test_ruff_selects_d1_for_serve():
    """The ruff config must keep pydocstyle D1 on for repro/serve —
    and the per-file-ignores must not carve serve back out."""
    text = open(os.path.join(ROOT, "ruff.toml")).read()
    assert re.search(r'select\s*=\s*\[[^]]*"D1', text), (
        "ruff.toml no longer selects D1xx (docstring presence)")
    for line in text.splitlines():
        if "serve" in line and "D1" in line and "ignore" in line:
            raise AssertionError(
                f"ruff.toml ignores D1 for serve: {line!r}")
