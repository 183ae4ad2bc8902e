"""The plain reference: seeded data and int64 numpy checks of the
deployments' guarantees.  Imports nothing of the program."""
