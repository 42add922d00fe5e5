"""Plain references, one module per system, found by the system's name."""
