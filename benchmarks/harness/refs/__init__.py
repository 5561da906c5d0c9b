"""Plain references the system under test is compared with."""
