"""Transaction-ingest tier: backpressured, batched mempool admission.

The production front door — concurrent ``submitTransaction`` RPC callers
and P2P tx relay — feeds a bounded per-source-fair queue (queue.py) whose
waves are admitted by a micro-batcher (tier.py): contextual pre-checks
stay on the mempool lock in arrival order, while signature+script
verification for the whole wave rides the verify plane off-lock as the
``standalone_tx`` coalescing traffic class.  Admission outcomes are
state-identical to the per-tx ``validate_and_insert_transaction`` path.
"""

from kaspa_tpu.ingest.queue import SOURCE_P2P, SOURCE_RPC, SOURCE_UNORPHAN, IngestQueue
from kaspa_tpu.ingest.tier import AdmissionTicket, IngestConfig, IngestTier

__all__ = [
    "SOURCE_P2P",
    "SOURCE_RPC",
    "SOURCE_UNORPHAN",
    "AdmissionTicket",
    "IngestConfig",
    "IngestQueue",
    "IngestTier",
]
