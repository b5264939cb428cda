"""Shared --platform plumbing for bench/stress entry points.

An explicit --platform pins jax through jax.config BEFORE any filodb import
touches jax, so a stage that measures host code can ask for the CPU."""
from __future__ import annotations


def add_platform_arg(ap) -> None:
    ap.add_argument("--platform", default="",
                    help="pin the jax platform (e.g. cpu)")


def apply_platform(args) -> None:
    if getattr(args, "platform", ""):
        import jax
        jax.config.update("jax_platforms", args.platform)
