"""The chip benchmark's harness: cell loading, the traffic generator, one
run of a cell, the plain reference, the comparison and the trace reduction."""
