"""The plain reference of hashgraph consensus and the DAG generator,
with the comparison behind `correct` (no import of the program)."""
