"""The yardstick of the chip benchmark: everything that decides what a run
measures and whether its answers are right.

Key sets, traffic generation, the closed-loop client model, the plain
reference, the trace reduction and the compile counter live here, beside
the benchmark, so that a change to the program under test cannot change
the measurement. From the program the benchmark takes only the system
under test (``ShardedUpLIF``, ``SelfTuner``, ``RequestGateway``).
"""
