"""Quickstart: the ERBIUM-on-TPU rule engine in five steps + a tiny LM.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (ErbiumEngine, compile_rules, generate_queries,
                        generate_rules)
from repro.core.encoder import queries_to_arrays


def main():
    # 1. offline: rules -> compiled dense interval table (the "NFA")
    ruleset = generate_rules(2_000, version=2, seed=0)
    table = compile_rules(ruleset)
    print(f"compiled {table.n_rules} rules x {table.n_cols} criteria "
          f"({table.memory_bytes() / 1e6:.1f} MB table, "
          f"{table.n_partitions} airport partitions)")

    # 2. online: the engine (Pallas kernel in interpret mode on CPU)
    engine = ErbiumEngine(table, n_engines=2, tile_b=256, tile_r=512)

    # 3. queries from the Domain-Explorer side
    queries = generate_queries(ruleset, 1_000, seed=1)
    decisions, weights, rule_ids = engine.match_queries(queries)
    hit = np.mean(np.asarray(weights) >= 0)
    print(f"matched {hit:.0%} of {len(queries)} MCT queries; "
          f"median MCT = {np.median(np.asarray(decisions)[np.asarray(decisions) >= 0]):.0f} min")

    # 4. hot rule update (the paper's 500 us NFA reload)
    us = engine.reload(generate_rules(2_000, version=2, seed=99))
    print(f"rule hot-reload (device table swap): {us:.0f} us")

    # 5. the LM side of the framework: one of the 10 assigned archs, reduced
    import jax
    from repro.configs.base import get_config
    from repro.models.registry import build_model, make_inputs
    cfg = get_config("gemma3-1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_inputs(cfg, 2, 32, rng=np.random.default_rng(0))
    print(f"gemma3-1b (reduced) loss = {float(model.loss(params, batch)):.3f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
