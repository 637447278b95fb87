"""Serving layer: the multi-tenant coreset-query serving engine
(`cluster`, DESIGN.md Sec. 13)."""

from repro.serve.cluster import (ClusterServeEngine, EngineStats,
                                 QueryTicket, StaticCenters)

__all__ = ["ClusterServeEngine", "EngineStats", "QueryTicket",
           "StaticCenters"]
