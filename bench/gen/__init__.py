"""Seeded generators: input pools, TA states, arrival schedules."""
