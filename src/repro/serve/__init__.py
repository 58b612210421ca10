"""Serving surface: the micro-batching ANN service.

``AnnService`` queues search and ingest requests, flushes them as batches
through any ``repro.api`` index (the batched scan engines of
``repro.ann``), and keeps the latency and decode-cache accounting.  This
package is the stable import point a serving deployment uses.
"""

from .ann_service import AddTicket, AnnService, BatchPolicy, Ticket

__all__ = ["AnnService", "AddTicket", "BatchPolicy", "Ticket"]
