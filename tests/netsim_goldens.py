"""Table-1 golden finish ticks, shared by the engine, window and kernel
tests.

Scenario: the Table-1 fabric (leaf-spine, 4 ToR x 4 spine, 32 hosts),
4 rings of 8, 1 MB chunks, 2 back-to-back passes, ``n_ticks=20_000``,
``window=64``, seed 3.  Captured with jax 0.9.0 on the CPU backend under
its default PRNG (``jax_threefry_partitionable=True``).

The earlier constants came from jax 0.4.37, whose default was
``jax_threefry_partitionable=False``.  That flag changes the
``jax.random.split``/``uniform`` stream behind the DCQCN rate-cut coin
flips (`stages.stage_rate_control`), and with it every finish tick that
depends on a rate cut.  The old values were: ecmp_base 10757, ecmp_sym
7900, balanced_sym 2239 (unchanged: balanced routing never congests),
ecmp_pq 10303.  The engine itself did not change: re-running it with the
flag set to False reproduces the old ecmp_base value.
"""

GOLDEN_JOB = {"ecmp_base": 12232, "ecmp_sym": 7964,
              "balanced_sym": 2239, "ecmp_pq": 9985}
GOLDEN_FLOWS_ECMP_BASE = [
    8444, 8468, 8375, 8236, 7788, 7483, 8021, 8283, 7289, 7174, 6946, 6856,
    6000, 6393, 6814, 7209, 9254, 9445, 9495, 7380, 7685, 8289, 8698, 8979,
    11265, 11703, 11964, 12152, 12232, 11883, 10032, 10729]
GOLDEN_FLOWS_ECMP_SYM = [
    7845, 7940, 7822, 7935, 7814, 7898, 7845, 7964, 7928, 7937, 7852, 7956,
    7827, 7919, 7871, 7948, 7882, 7939, 7920, 7915, 7779, 7909, 7853, 7934,
    7843, 7926, 7870, 7946, 7777, 7864, 7851, 7945]
