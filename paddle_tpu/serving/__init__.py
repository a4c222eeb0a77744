"""Serving engine — the inference-side counterpart of the training stack.

The reference deploys trained models through `paddle/capi` and the C++
inference library (`inference/io.h`): load a merged config+parameter
blob, then call the GradientMachine forward per request, one request at
a time, re-running the whole network per decode step.  On an XLA
device that shape of serving loses twice: every new input shape
recompiles, and sequence generation re-pays the full O(L^2) forward per
emitted token.

This package is the TPU-native replacement:

* ``InferenceEngine`` (engine.py) — loads a ``save_inference_model``
  artifact (or any pruned program), pads requests into a small set of
  shape buckets with per-bucket compiled-executable reuse, keeps the
  weights device-resident, and exposes bucket hit/miss counters — zero
  recompiles in steady state.
* ``TransformerGenerator`` / ``FullRerunDecoder`` (decoder.py) —
  KV-cache incremental decoding for the Transformer: one O(S^2) prefill
  per request, then O(L) per emitted token against preallocated
  [B, L, h, d] caches, with greedy and beam front-ends reusing the
  beam_search / beam_search_decode ops.  FullRerunDecoder is the honest
  O(L^2) baseline the bench compares against.
* ``ContinuousBatchingScheduler`` (scheduler.py) — a request queue
  admitting prompts into fixed in-flight batch slots with per-slot done
  masks; finished sequences retire and new requests backfill their slot
  without recompilation; ``serve()`` runs the loop on a thread with
  per-request latency accounting.  Page-aware models are admitted by
  page budget (admit while free pages last; structurally infeasible
  prompts reject with ``PoolCapacityError`` instead of hanging).
* ``PagedTransformerGenerator`` (paged_decoder.py) + ``PageAllocator``
  (paging.py) — the ISSUE-6 tentpole: block-table paged KV over ONE
  pooled tensor, a Pallas ragged decode-attention kernel, chunked
  causal prefill interleaved with decode in one compiled dispatch, and
  copy-on-write prefix sharing with refcounts.  The dense decoder stays
  as the differential parity baseline.
* ``SpeculativeGenerator`` (speculative.py) + ``constraints.py`` — the
  ISSUE-15 tentpole: draft k tokens with a cheap draft model, verify
  all k in ONE target dispatch (``verify_step``'s per-lane token axis
  over the paged pool), accept/reject with host-side page-table
  truncation + pre-write copy-on-write, and per-request grammar/JSON
  constrained generation via in-graph token masks fed as data.
  Token-for-token parity with plain greedy at any accept rate.
* ``SessionStore`` (sessions.py) + the tiered ``PageAllocator`` host
  pool — the ISSUE-20 tentpole: evicted prefix chunks DEMOTE to pinned
  host RAM instead of being destroyed (promoted back bitwise-identical
  on the next hit), and whole lanes suspend/resume through checksummed
  fingerprint-keyed host/disk artifacts — a session id on
  ``/v1/generate`` continues a conversation without re-prefill.
* ``gateway/`` (ISSUE 10) — the production front door: ``ModelRegistry``
  (versioned artifacts, HBM budget, zero-downtime hot swap),
  ``TenantRouter`` (token buckets, SLO-class admission, fair share),
  ``Gateway``/``TokenStream`` (streaming + cancellation + request
  journal), and the ``GatewayServer`` HTTP surface — imported as
  ``paddle_tpu.serving.gateway`` (kept out of this namespace so plain
  serving users do not pay the HTTP imports).
"""

from .engine import InferenceEngine  # noqa: F401
from .decoder import FullRerunDecoder, TransformerGenerator  # noqa: F401
from .paged_decoder import (PagedTransformerGenerator,  # noqa: F401
                            copy_weights, kv_page_bytes)
from .paged_lm import PagedLMGenerator  # noqa: F401
from .paging import PageAllocator, PageGroup, PoolCapacityError  # noqa: F401
from .scheduler import (ContinuousBatchingScheduler, Request,  # noqa: F401
                        RequestCancelled, SchedulerShutdown)
from .constraints import (Constraint, DFAConstraint,  # noqa: F401
                          TokenSetConstraint, compile_constraint)
from .speculative import SpeculativeGenerator  # noqa: F401
from .sessions import SessionStore  # noqa: F401

__all__ = ["InferenceEngine", "TransformerGenerator", "FullRerunDecoder",
           "PagedTransformerGenerator", "PagedLMGenerator", "PageAllocator",
           "PageGroup", "copy_weights",
           "kv_page_bytes", "PoolCapacityError",
           "ContinuousBatchingScheduler", "Request", "RequestCancelled",
           "SchedulerShutdown", "SpeculativeGenerator", "Constraint",
           "TokenSetConstraint", "DFAConstraint", "compile_constraint",
           "SessionStore"]
