"""One module per model family: seeded weights in the program's layout,
the program's training entry, the work counts and the plain reference.
A configuration names its family; a new family is a new module here."""
