"""Serving driver: batched generation with coordinator-backed model
version discovery (leased zero-roundtrip reads).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --preset tiny --requests 4
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke
"""

from __future__ import annotations

import argparse

import jax

from ..configs import get_arch
from ..configs.base import ArchConfig
from ..coord.registry import ClusterRegistry
from ..models import init_params
from ..serve.engine import Engine, ServeConfig
from .runtime import describe_devices, enable_compile_cache
from .train import PRESETS


def fresh_init_manifest(cfg: ArchConfig) -> dict:
    """The manifest committed for randomly initialised weights."""
    return {"step": 0, "path": "(fresh init)", "sha256": "0" * 64,
            "n_arrays": 0, "extra": {"arch": cfg.name}}


def start_engine(cfg: ArchConfig, serve_cfg: ServeConfig,
                 consistency: str = "leaseguard") -> Engine:
    """Stand up the coordinator, commit the model's manifest and build
    an engine that discovers it with a leased read."""
    registry = ClusterRegistry(consistency=consistency)
    registry.commit_checkpoint(fresh_init_manifest(cfg))
    params = init_params(jax.random.PRNGKey(0), cfg)
    return Engine(cfg, params, serve_cfg, registry=registry)


def random_prompts(cfg: ArchConfig, requests: int,
                   prompt_len: int) -> jax.Array:
    return jax.random.randint(jax.random.PRNGKey(1), (requests, prompt_len),
                              0, cfg.vocab_size)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=sorted(PRESETS), default="tiny")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    from ..consistency import benchmark_configs
    ap.add_argument("--consistency", default="leaseguard",
                    choices=sorted(benchmark_configs(variants=False)),
                    help="coordination read policy for model-version reads")
    args = ap.parse_args()
    enable_compile_cache()

    if args.arch:
        cfg = get_arch(args.arch)
        if args.smoke:
            cfg = cfg.reduced()
    else:
        cfg = PRESETS[args.preset]

    engine = start_engine(cfg, ServeConfig(max_new_tokens=args.max_new,
                                           temperature=args.temperature),
                          consistency=args.consistency)
    out = engine.generate(random_prompts(cfg, args.requests,
                                         args.prompt_len))
    print(f"served {args.requests} requests, generated {out.shape[1]} "
          f"tokens each on {describe_devices()}; coordinator stats: "
          f"{engine.registry.coord.stats()}")


if __name__ == "__main__":
    main()
