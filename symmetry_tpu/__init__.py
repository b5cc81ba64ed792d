"""symmetry-tpu: a TPU-native decentralized P2P AI-inference framework.

A ground-up rebuild of the capabilities of shlebbypops/symmetry (symmetry-cli,
/root/reference) — a P2P network where provider nodes join an encrypted swarm,
register with a routing server, and stream chat completions directly to peers —
with the inference engine itself implemented natively on TPU via JAX/XLA/Pallas
instead of proxying to an external GPU server.

Three roles (reference: readme.md Architecture diagram):
  - server   (symmetry_tpu.server):   session broker / model router / balancer
  - provider (symmetry_tpu.provider): model host; `tpu_native` engine or HTTP proxy
  - client   (symmetry_tpu.client):   requests a provider, streams completions
"""

import time

# The first statement this program runs in a process: where its start-up
# timeline begins when the kernel's own stamp cannot be used
# (utils/trace.py process_start).
T_FIRST_STATEMENT = time.monotonic()

__version__ = "0.1.0"
