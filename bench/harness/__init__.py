"""The benchmark's harness: cell lookup, data and traffic generation, the
plain reference, the trace reduction and the run itself.  Nothing here
is imported by the program under test."""
