"""Plain references, one module per configuration family.  They import
nothing of the program under test."""
