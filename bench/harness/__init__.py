"""The benchmark's own machinery: discovery of cells, configurations,
traffic mixes and metric readers by name, the traffic generator, the
seeded weights, the plain reference, the trace reduction and the
roofline arithmetic.  Nothing here imports the program under test at
module level, so the CPU tests can import every module."""
