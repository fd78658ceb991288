# Billing-faithful egress layer: the cloud store simulator (eq. 1 metering,
# per-consumer attribution), the deployable dollar-aware cache with its
# offline-exact audit, and the one replacement machine (LRU/LFU/GDS/GDSF)
# that the live cache and its shadows share. The online governance layer
# (repro.online) subscribes to EgressCache's AccessEvent stream from above.
from .store import BillingMeter, ObjectStore
from .replacement import ReplacementMachine
from .cache import (ONLINE_POLICIES, AccessEvent, AdmissionController,
                    AuditReport, EgressCache)

__all__ = [
    "BillingMeter", "ObjectStore", "ONLINE_POLICIES", "AccessEvent",
    "AdmissionController", "AuditReport", "EgressCache", "ReplacementMachine",
]
