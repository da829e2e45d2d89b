"""Serving APIs.

Max-flow serving (the WBPR paper's workload): ``MaxflowService`` —
shape-bucketed microbatching over the batched solver core with result
caching and warm-started re-solves.  See ``repro.serving.maxflow_service``.
"""
from repro.serving.maxflow_service import (MaxflowResult,  # noqa: F401
                                           MaxflowService, ServiceConfig)
from repro.serving.policy import (BucketModePolicy,  # noqa: F401
                                  candidate_modes)
