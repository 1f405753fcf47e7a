"""Light client (reference: light/).

 - verifier: pure header verification (adjacent / non-adjacent / backwards)
 - client: Client with sequential + skipping (bisection) modes, trust anchor
   options, trusted-store persistence, witness cross-checking
 - detector: divergence detection + LightClientAttackEvidence construction
 - provider: Mock / local-node / JSON-RPC light-block providers
 - store: DB-backed trusted store
 - range_verify: the sequential client's windows: the light prefixes of a
   bounded run of headers in chunk-sized launches, the per-header loop's
   verdict and side effects (BASELINE config 3)
 - gateway: LightGateway serving many concurrent clients (verified-answer
   cache, provider failover/hedging/scoreboard, typed degradation)
"""

from tendermint_tpu.light.client import SEQUENTIAL, SKIPPING, Client, TrustOptions
from tendermint_tpu.light.gateway import ErrGatewayDegraded, LightGateway
from tendermint_tpu.light.provider import (
    HTTPProvider,
    MockProvider,
    NodeProvider,
    Provider,
)
from tendermint_tpu.light.range_verify import verify_header_range
from tendermint_tpu.light.store import DBStore
from tendermint_tpu.light.verifier import (
    DEFAULT_TRUST_LEVEL,
    LightClientError,
    verify,
    verify_adjacent,
    verify_backwards,
    verify_non_adjacent,
)

__all__ = [
    "Client",
    "TrustOptions",
    "LightGateway",
    "ErrGatewayDegraded",
    "SEQUENTIAL",
    "SKIPPING",
    "Provider",
    "MockProvider",
    "NodeProvider",
    "HTTPProvider",
    "DBStore",
    "verify",
    "verify_adjacent",
    "verify_non_adjacent",
    "verify_backwards",
    "verify_header_range",
    "DEFAULT_TRUST_LEVEL",
    "LightClientError",
]
