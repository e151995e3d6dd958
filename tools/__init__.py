"""Repo tooling. This package marker exists so ``python -m tools.lint``
resolves; the standalone scripts here (tier1_diff.py, serve_smoke.py,
chaos_serve.py, ...) keep their own ``sys.path`` bootstraps and still
run file-direct."""
