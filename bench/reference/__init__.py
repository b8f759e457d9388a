"""Plain references that import nothing of the system under test."""
