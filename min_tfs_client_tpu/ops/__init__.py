"""TPU compute ops: Pallas kernels + jnp references, a module a
mechanism:

 * `ops.attention`  softmax attention: the flash kernel a prefill runs,
   the paged kernel of the decode pool, the rows kernel of T5's whole
   generations, the latent step's kernel (a decode step of latent
   attention over a cache it writes in place and reads by length:
   models/latent.py calls it), and their jnp references (re-exported
   below: every family attends);
 * `ops.ssm`        state-space (Mamba-2) mixing: the chunked scan and
   the one-token step over a float32 recurrent state
   (models/granite_hybrid.py);
 * `ops.kda`        delta-rule linear attention with a per-channel decay
   (KDA): the chunked form and the one-token step over a float32 state
   (models/ling_hybrid.py);
 * `ops.mhc`        the hyper-connected residual path (mHC): a token's n
   streams, the three per-token maps and Sinkhorn's rounds, in plain
   jax.numpy (models/xing.py).

`ssm`, `kda` and `mhc` are imported by the model that runs them
(`from min_tfs_client_tpu.ops import ssm`), not here: a family's boot
loads its own mechanism's module and no other's.
"""

from min_tfs_client_tpu.ops.attention import (  # noqa: F401
    attention,
    attention_reference,
    flash_attention,
)
