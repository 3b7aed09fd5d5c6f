"""Repo tooling package (`python -m scripts.graftlint`, static analysis)."""
