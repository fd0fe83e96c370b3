"""GraphMP reproduction: I/O-efficient big-graph analytics (single machine).

Public surface: ``GraphSession`` (the one entry point for analytics —
preprocess once, run many applications over a shared compressed cache),
``EngineConfig`` for tuning, and ``register_app`` for new vertex programs.

    from repro import GraphSession, preprocess_graph, write_edge_list

    write_edge_list(edges_dir, [(src, dst)])
    store = preprocess_graph(edges_dir, graph_dir)
    with GraphSession(store, cache_budget_bytes=1 << 28) as s:
        pr = s.run("pagerank", max_iters=30)
"""
# lazy attribute exports (PEP 562) keep `import repro` light — jax-heavy
# modules load on first touch of the corresponding name.
_EXPORTS = {
    "GraphSession": ("repro.session", "GraphSession"),
    "EngineConfig": ("repro.core.engine", "EngineConfig"),
    "VSWEngine": ("repro.core.engine", "VSWEngine"),
    "RunResult": ("repro.core.engine", "RunResult"),
    "BatchRunResult": ("repro.core.engine", "BatchRunResult"),
    "IterationStats": ("repro.core.engine", "IterationStats"),
    "register_app": ("repro.core.apps", "register_app"),
    "get_app": ("repro.core.apps", "get_app"),
    "available_apps": ("repro.core.apps", "available_apps"),
    "VertexProgram": ("repro.core.apps", "VertexProgram"),
    "BatchedVertexProgram": ("repro.core.apps", "BatchedVertexProgram"),
    "CompressedShardCache": ("repro.core.cache", "CompressedShardCache"),
    "ShardPipeline": ("repro.core.pipeline", "ShardPipeline"),
    "ShardSource": ("repro.graph.source", "ShardSource"),
    "MissingGraphError": ("repro.graph.source", "MissingGraphError"),
    "ConcurrentMutationError": ("repro.graph.source",
                                "ConcurrentMutationError"),
    "DeltaGraphStore": ("repro.graph.delta", "DeltaGraphStore"),
    "DeltaBudgetError": ("repro.graph.delta", "DeltaBudgetError"),
    "compact": ("repro.graph.compact", "compact"),
    "CompactionReport": ("repro.graph.compact", "CompactionReport"),
    "GraphStore": ("repro.graph.storage", "GraphStore"),
    "PackedGraphStore": ("repro.graph.packed", "PackedGraphStore"),
    "MemoryGraphStore": ("repro.graph.memory", "MemoryGraphStore"),
    "pack_graph": ("repro.graph.packed", "pack_graph"),
    "write_edge_list": ("repro.graph.storage", "write_edge_list"),
    "preprocess_graph": ("repro.graph.preprocess", "preprocess_graph"),
    "rmat_edges": ("repro.graph.generate", "rmat_edges"),
    "uniform_edges": ("repro.graph.generate", "uniform_edges"),
    "zipf_edges": ("repro.graph.generate", "zipf_edges"),
    "materialize": ("repro.graph.generate", "materialize"),
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    try:
        module, attr = _EXPORTS[name]
    except KeyError:
        raise AttributeError(f"module 'repro' has no attribute {name!r}") from None
    import importlib

    value = getattr(importlib.import_module(module), attr)
    globals()[name] = value  # cache for subsequent lookups
    return value


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
