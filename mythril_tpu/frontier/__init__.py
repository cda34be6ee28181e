"""Batched device-resident frontier interpreter.

The north-star architecture (SURVEY.md §7.1): instead of stepping one
host-Python ``GlobalState`` at a time (reference
mythril/laser/ethereum/svm.py:261-304), the work list becomes a fixed-width
struct-of-arrays batch of machine states held on the TPU.  One jitted segment
program steps every live path in lockstep for K instructions per dispatch —
opcode dispatch via ``lax.switch``, 256-bit words as 16-bit-limb tensors,
symbolic values as indices into a device-resident term arena, JUMPI forks as
masked in-batch duplication — and the host only sees the batch at segment
boundaries to harvest finished paths, fire detector hooks, and refill slots.

Module map:
  * ``ops``     — arena/term op codes + handler family codes (shared constants)
  * ``arena``   — host mirror of the device term arena; encode/decode vs
                  the host term IR (mythril_tpu/smt/terms.py)
  * ``code``    — per-instruction dispatch tables compiled from bytecode
  * ``state``   — the SoA frontier state pytree + host mirrors
  * ``step``    — the jitted K-step segment program
  * ``records`` — host-side path lineage (fork tree) bookkeeping
  * ``walker``  — carrier reconstruction: replays device events through host
                  GlobalStates so detection modules see identical states
  * ``engine``  — orchestration + LaserEVM integration
"""

__all__ = ["FrontierEngine"]


def __getattr__(name: str):
    # lazy: detection modules import frontier.taint (jax-free) at load time;
    # an eager engine import here would pull step -> jax into every detector
    # load and defeat svm.py's deliberately-lazy FrontierEngine import
    if name == "FrontierEngine":
        from mythril_tpu.frontier.engine import FrontierEngine

        return FrontierEngine
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
